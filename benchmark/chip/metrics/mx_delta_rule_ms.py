"""Device milliseconds a step under the ``mx.delta_rule`` scope
(``ops/lm_ops.py:gated_delta_rule_chunked``, the chunked delta rule alone;
it lies inside ``mx.gdn``): see ``scope_ms.py``. From the device trace."""
import scope_ms

PREFIX = "mx.delta_rule"


def read(run):
    return scope_ms.read(run, PREFIX)
