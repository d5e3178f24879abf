"""Process start to the first measured step: imports, net, batches, the
reference check, compile or cache load, warm-up. Host clock."""


def read(run):
    return run["setup_s"]
