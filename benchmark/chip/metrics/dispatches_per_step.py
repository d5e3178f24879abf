"""Executables a step's spans say they launched (the sum of ``programs``
over the step's spans): median over the traced steps. What the framework
knows it launches, to stand beside ``programs_per_step`` from the device. From
the program's spans."""
import program_spans


def read(run):
    return program_spans.median(
        s["programs"] for s in program_spans.steps())
