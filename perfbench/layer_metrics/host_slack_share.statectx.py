"""The host's ``device_wait`` seconds over seconds inside ``step()``, since the
window opened, percent (``engine.stats()["units"]``)."""
from perfbench.harness.unit_reads import (  # noqa: F401
    host_slack_share as read)
