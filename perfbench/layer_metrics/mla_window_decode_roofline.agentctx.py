"""``mla_window_decode``: the larger of the window's latent bytes / HBM peak
and absorbed-form FLOPs / bf16 peak, over its device time; the tokens inside
the window (min(context, window) a row a layer), not the pages around it,
so the block fetch caps it at about 80% in pages of 128."""
from perfbench.harness.window_latent import (  # noqa: F401
    mla_window_decode_roofline as read)
