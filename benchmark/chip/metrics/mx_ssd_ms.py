"""Device milliseconds a step under the ``mx.ssd`` scope
(``ops/lm_ops.py:ssd_chunked``, the chunked recurrence alone; it lies inside
``mx.mamba2``): see ``scope_ms.py``. From the device trace."""
import scope_ms

PREFIX = "mx.ssd"


def read(run):
    return scope_ms.read(run, PREFIX)
