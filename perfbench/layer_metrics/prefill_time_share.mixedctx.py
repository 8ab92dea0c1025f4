"""Host time inside prefill steps over the window, in percent."""
from perfbench.harness.reads import prefill_time_share as read  # noqa: F401
