"""Device milliseconds a step under the ``mx.swa`` scope
(``ops/lm_ops.py:fused_qkv_attention`` in a window layer: the fused
projection, the partial rotary embedding, the KV heads' repeat, the window
kernels with their sink and the output projection): see ``scope_ms.py``.
From the device trace."""
import scope_ms

PREFIX = "mx.swa"


def read(run):
    return scope_ms.read(run, PREFIX)
