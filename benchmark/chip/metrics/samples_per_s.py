"""Samples whose step completed in the window over the time from the first
completion to the last, all steps of the window. Host clock."""


def read(run):
    done = run["done"]
    if len(done) > 1:
        return run["traffic"]["batch"] * (len(done) - 1) / (done[-1] - done[0])
