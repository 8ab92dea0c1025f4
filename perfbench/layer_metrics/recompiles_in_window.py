"""Programs handed to the compiler inside the window; should read 0."""


def read(run):
    return float(run["compile"]["window_compiles"])
