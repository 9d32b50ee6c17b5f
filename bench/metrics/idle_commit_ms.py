"""Device idle per traced step while the engine catches the drafters up
and commits tokens, in ms: idle whose innermost program span is
``spin.catchup`` or ``spin.commit`` (``harness/program_spans.py``)."""

from harness import program_spans


def read(run):
    return program_spans.idle_ms(run, "commit")
