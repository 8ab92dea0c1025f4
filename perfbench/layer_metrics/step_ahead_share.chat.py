"""Units launched while another was in flight over units launched, since the
window opened, percent (``engine.stats()["ahead"]``)."""
from perfbench.harness.ahead_reads import (  # noqa: F401
    step_ahead_share as read)
