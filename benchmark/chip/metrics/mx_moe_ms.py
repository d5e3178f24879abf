"""Device milliseconds a step under the ``mx.moe.route`` and
``mx.moe.experts`` scopes (``parallel/moe.py:dropless_moe_ffn``: scores,
top-k and plan; the routed loops and the shared expert): see ``scope_ms.py``.
From the device trace."""
import scope_ms

PREFIX = "mx.moe"


def read(run):
    return scope_ms.read(run, PREFIX)
