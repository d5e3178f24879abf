"""``dispatches_per_step`` in the ``FitLoop`` cell: executables the spans of
a step say they launched (the sum of ``programs``), for the steps that an
``mx.fit.step`` root closes; median over the traced steps. Under a name of
its own because ``program_spans.CLOSERS``, which the other cells' metric
reads, does not know that root. See ``step_spans.py``. From the program's
spans."""
import step_spans


def read(run):
    return step_spans.programs()
