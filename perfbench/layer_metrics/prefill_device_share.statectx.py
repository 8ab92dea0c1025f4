"""Prefill units' time on the device over all units', since the window opened,
percent (``engine.stats()["units"]``)."""
from perfbench.harness.unit_reads import (  # noqa: F401
    prefill_device_share as read)
