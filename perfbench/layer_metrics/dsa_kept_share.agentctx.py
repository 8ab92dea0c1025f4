"""Cache entries attended over entries scored by the full layers' decode
steps since the window opened, percent."""
from perfbench.harness.sparse_reads import dsa_kept_share as read  # noqa: F401
