"""Device milliseconds a step under the ``mx.moe.combine`` scope
(``parallel/moe.py:_add_rows``, inside ``mx.moe.experts``: a tile's rows
added into the routed loop's float32 accumulator, forward and backward; the
kernel ``mx_moe_combine`` where the rows are float32 and D a multiple of
128, else XLA's scatter-add): see ``scope_ms.py``. From the device trace;
nothing where no op carries the scope."""
import scope_ms

PREFIX = "mx.moe.combine"


def read(run):
    return scope_ms.read(run, PREFIX)
