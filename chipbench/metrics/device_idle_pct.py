"""1 - union of device-operation intervals / traced window, over a window
of two whole epochs with the boundary work between and after them."""


def read(run):
    return 100.0 * run["trace"]["idle_share"] if run["trace"] else None
