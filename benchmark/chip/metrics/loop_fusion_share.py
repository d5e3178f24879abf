"""Share of the device's op time spent in loop and input fusions (BatchNorm
statistics and apply, ReLU, residual add, casts: the HBM-bound chains) as
against everything else (convolution fusions above all). From the trace's
fusion kinds."""
MEMORY_BOUND = ("kLoop", "kInput")


def read(run):
    if run["trace"]:
        by_kind = run["trace"]["seconds_by_kind"]
        return 100.0 * sum(by_kind.get(k, 0.0) for k in MEMORY_BOUND) \
            / sum(by_kind.values())
