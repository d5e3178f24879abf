"""1 - (union of the device's op intervals) / (traced window), in percent;
mean over devices."""


def read(run):
    if run["trace"]:
        return 100.0 * (1.0 - run["trace"]["busy_s"] / run["trace"]["window_s"])
