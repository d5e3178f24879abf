"""Device milliseconds a step under the ``mx.gdn`` scope
(``ops/lm_ops.py:gated_deltanet_mixer``: a Gated DeltaNet layer's
projections, convolutions, norms, gate and, inside it, ``mx.delta_rule``):
see ``scope_ms.py``. From the device trace."""
import scope_ms

PREFIX = "mx.gdn"


def read(run):
    return scope_ms.read(run, PREFIX)
