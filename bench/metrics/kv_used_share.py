"""KV cells in use over KV cells allocated, in %: the mean over the
traced ``spin.step`` spans of their ``kv_used / kv_alloc``, summed over
the target's pool and every drafter's (``harness/program_spans.py``)."""

from harness import program_spans


def read(run):
    p = program_spans.phases(run)
    return None if p is None else p.kv_used_share
