"""``logits_copy``, percent of the program's ``engine.step`` spans that
ended in the window."""
from perfbench.harness.phase_reads import (  # noqa: F401
    logits_copy_share as read)
