"""Device idle per traced step while the engine assigns drafters and
drafts, in ms: idle whose innermost program span is ``spin.assign``,
``spin.draft`` or ``spin.precompute`` (``harness/program_spans.py``)."""

from harness import program_spans


def read(run):
    return program_spans.idle_ms(run, "draft")
