"""Device idle per traced step while the engine verifies and rolls the
target's KV back, in ms: idle whose innermost program span is
``spin.verify`` or ``spin.rollback`` (``harness/program_spans.py``)."""

from harness import program_spans


def read(run):
    return program_spans.idle_ms(run, "verify")
