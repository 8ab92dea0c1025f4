"""Tokens trained in the window over its seconds over the chips; host clock
from the first measured step's dispatch to the last one's loss being ready."""
from perfbench.harness.arith import rate


def read(run):
    if run["job"] != "train":
        return None
    return rate(run["train"]["tokens"], run["window"]["seconds"],
                run["chips"])
