"""``ssm_state_decode``: the larger of the float32 states read and written
(and each row's inputs and output) over the HBM peak and its FLOPs over the
bf16 peak, over its device time in the traced decode steps."""
from perfbench.harness.ssm_reads import (  # noqa: F401
    ssm_decode_roofline as read)
