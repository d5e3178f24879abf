"""Host milliseconds of a step inside ``mx.cached_op.prepare``, the first
child of a hybridized block's replay (the parameter list, the random key and
the four small programs it launches, the signature, the cache lookup): a
step's sum, median over the traced steps. See ``step_spans.py``. From the
program's spans."""
import step_spans


def read(run):
    return step_spans.sum_ms(step_spans.PREPARE)
