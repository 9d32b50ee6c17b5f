"""Device idle per traced step while the engine schedules, in ms: idle
whose innermost program span is ``spin.schedule``, ``spin.admit`` or
``spin.prefill`` (``harness/program_spans.py``)."""

from harness import program_spans


def read(run):
    return program_spans.idle_ms(run, "schedule")
