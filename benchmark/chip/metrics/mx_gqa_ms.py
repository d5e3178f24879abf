"""Device milliseconds a step under the ``mx.gqa`` scope
(``ops/lm_ops.py:gqa_attention``: the four projections, the repeat of the KV
heads and the attention kernels): see ``scope_ms.py``. From the device
trace."""
import scope_ms

PREFIX = "mx.gqa"


def read(run):
    return scope_ms.read(run, PREFIX)
