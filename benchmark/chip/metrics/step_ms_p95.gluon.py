"""The end-to-end step tail's arithmetic on the untraced part of the traced
run: where the host paces the step, its tail is host jitter and cannot decide
a PR, so it is recorded here."""
from step_ms_p95 import read  # noqa: F401
