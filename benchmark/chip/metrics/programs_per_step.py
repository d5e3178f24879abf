"""XLA module launches on a device per step, from the trace's "XLA Modules"
line; mean over devices."""


def read(run):
    if run["trace"]:
        return run["trace"]["launches_per_step"]
