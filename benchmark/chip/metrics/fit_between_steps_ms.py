"""Host milliseconds between two ``FitLoop`` steps: from the end of a step's
``mx.fit.fetch`` (the loss and the finite flag are on the host) to the start
of the next step's ``mx.cached_op.forward``: ``mx.fit.close``, the
iterator's ``next``, and what ``CachedOp.__call__`` does before its span
opens. Nothing is in flight then, so the device waits all of it. Median over
the traced steps. See ``step_spans.py``. From the program's spans."""
import step_spans


def read(run):
    return step_spans.between_ms()
