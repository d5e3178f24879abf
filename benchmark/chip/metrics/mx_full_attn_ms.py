"""Device milliseconds a step under the ``mx.full_attn`` scope
(``ops/lm_ops.py:fused_qkv_attention`` in a full layer: the same as
``mx.swa``'s, causal over the whole sequence, no sink): see
``scope_ms.py``. From the device trace."""
import scope_ms

PREFIX = "mx.full_attn"


def read(run):
    return scope_ms.read(run, PREFIX)
