"""Host milliseconds of a step inside ``Trainer.step`` (``mx.trainer.step``:
the all-reduce, where there is one, and the update's bucket programs): median
over the traced steps. From the program's spans."""
import program_spans


def read(run):
    return program_spans.root_ms(program_spans.UPDATE)
