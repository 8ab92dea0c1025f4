"""Seconds the engine had handed the device nothing, percent of the traced
stretch (``engine.step`` spans' ``starved_*``): beside ``device_idle_share``."""
from perfbench.harness.unit_reads import (  # noqa: F401
    device_starved_share as read)
