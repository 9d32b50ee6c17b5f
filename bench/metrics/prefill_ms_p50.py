"""Median prefill time, in ms: for each request the program admitted in
the window, from the grant of its row to the read-back of its first
token (``Request.host_admitted`` to ``Request.host_first_token``, both
stamped by the program on the harness's clock)."""

from harness.stats import percentile


def samples(run):
    out = []
    for r in run.requests.values():
        a = getattr(r.req, "host_admitted", None)
        f = getattr(r.req, "host_first_token", None)
        if a is not None and f is not None and run.in_window(a):
            out.append((f - a) * 1e3)
    return out


def read(run):
    return percentile(samples(run), 50)
