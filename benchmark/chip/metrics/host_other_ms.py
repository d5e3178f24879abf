"""Host milliseconds of a step outside forward, backward and update: the
step's period (end of one ``mx.trainer.step`` to the end of the next) less
the three, so the eager loss, ``astype``, ``mean``, the user's loop and the
harness. Median over the traced steps. From the program's spans."""
import program_spans


def read(run):
    return program_spans.median(
        program_spans.other_ms(s) for s in program_spans.steps())
