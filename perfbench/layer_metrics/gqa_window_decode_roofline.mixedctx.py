"""``gqa_window_decode``: the same over the tokens inside the window (min(context,
window) a row a layer); the pages around the window are not counted."""
from perfbench.harness.gqa_reads import (  # noqa: F401
    gqa_window_decode_roofline as read)
