"""Host milliseconds of a step inside ``autograd``'s reverse pass
(``mx.autograd.backward``: the tape walk, the net's backward program,
handing the gradients to their buffers): median over the traced steps. From
the program's spans."""
import program_spans


def read(run):
    return program_spans.root_ms(program_spans.BACKWARD)
