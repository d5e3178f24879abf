"""Device milliseconds a step under the ``mx.mla`` scope
(``ops/lm_ops.py:mla_attention``: the projections, RoPE and the attention
kernels of every layer, the MTP module's too): see ``scope_ms.py``. From the
device trace."""
import scope_ms

PREFIX = "mx.mla"


def read(run):
    return scope_ms.read(run, PREFIX)
