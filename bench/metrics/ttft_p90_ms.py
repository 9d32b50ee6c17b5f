"""Time to first token, 90th percentile, in ms: from when each request
was due to when its first token was delivered, over every request whose
first token falls in the window."""

from harness.stats import percentile


def samples(run):
    return [(r.first_token - r.due) * 1e3 for r in run.requests.values()
            if r.first_token is not None and run.in_window(r.first_token)]


def read(run):
    return percentile(samples(run), 90)
