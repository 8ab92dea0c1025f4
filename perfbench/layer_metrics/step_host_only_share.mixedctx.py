"""Every phase of ``engine.step`` but ``dispatch`` / ``device_wait``, percent
of the traced stretch: read beside ``device_idle_share``."""
from perfbench.harness.phase_reads import (  # noqa: F401
    host_only_share as read)
