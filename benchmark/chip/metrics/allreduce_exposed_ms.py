"""Milliseconds per step in which a collective runs on a device and no
other op does; median over devices. From the trace."""


def read(run):
    if run["trace"]:
        return 1e3 * run["trace"]["collective_exposed_s_per_step"]
