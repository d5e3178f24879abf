"""Host milliseconds of a step inside the replay of the hybridized net
(``mx.cached_op.forward`` spans with no parent): median over the traced
steps. From the program's spans."""
import program_spans


def read(run):
    return program_spans.root_ms(program_spans.FORWARD)
