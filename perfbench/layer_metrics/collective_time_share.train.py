"""Device time in all-reduce / all-gather / all-to-all / collective-permute /
reduce-scatter over the traced stretch, in percent, averaged over the chips.
Only a cell across chips has any."""


def read(run):
    t = run["trace"]
    if run["job"] != "train" or run["chips"] < 2 or not t or not t["window_s"]:
        return None
    return 100.0 * t["collective_s"] / t["window_s"]
