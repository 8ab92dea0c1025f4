"""Process start to the opening of the window: imports, weights made on the
device from the seed, cache load or compile, warm-up of the cell's own
shapes, the reference check and, where the cell serves, the ramp."""


def read(run):
    return run["setup_s"]
