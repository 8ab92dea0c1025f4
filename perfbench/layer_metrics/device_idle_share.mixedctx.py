"""1 - (union of device-operation intervals over the traced stretch), percent."""
from perfbench.harness.reads import idle_share as read  # noqa: F401
