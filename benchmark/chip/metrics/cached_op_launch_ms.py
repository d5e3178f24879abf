"""Host milliseconds of a step inside ``mx.cached_op.launch``, the second
child of a hybridized block's replay (the residual set to donate and the
call of the jitted program with its few hundred buffers): a step's sum,
median over the traced steps. See ``step_spans.py``. From the program's
spans."""
import step_spans


def read(run):
    return step_spans.sum_ms(step_spans.LAUNCH)
