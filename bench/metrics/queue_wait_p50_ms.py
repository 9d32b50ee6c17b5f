"""Median wait before prefill, in ms: for each request due in the
window, from when it was due to when the program granted it a row and
began its prefill (``Request.host_admitted``, stamped by the program on
the harness's clock).  Requests not yet admitted when the window closes
are left out."""

from harness.stats import percentile


def samples(run):
    out = []
    for r in run.requests.values():
        t = getattr(r.req, "host_admitted", None)
        if t is not None and run.in_window(r.due):
            out.append((t - r.due) * 1e3)
    return out


def read(run):
    return percentile(samples(run), 50)
