"""Device milliseconds a step under none of the scopes the cell's other
metrics read: see ``scope_ms.py``. From the device trace."""
import scope_ms


def read(run):
    return scope_ms.rest(run)
