"""Device milliseconds a step under the ``mx.mamba2`` scope
(``ops/lm_ops.py:mamba2_mixer``: a Mamba-2 layer's projections, convolution,
gated norm and, inside it, ``mx.ssd``): see ``scope_ms.py``. From the device
trace."""
import scope_ms

PREFIX = "mx.mamba2"


def read(run):
    return scope_ms.read(run, PREFIX)
