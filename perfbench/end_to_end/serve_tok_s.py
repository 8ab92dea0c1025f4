"""Prompt tokens whose prefill step ended inside the window plus output
tokens emitted inside it, over the window's seconds."""
from perfbench.harness.arith import rate
from perfbench.harness.reads import served_tokens


def read(run):
    n = served_tokens(run)
    return None if n is None else rate(n, run["window"]["seconds"])
