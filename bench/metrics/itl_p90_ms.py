"""Inter-token latency, 90th percentile, in ms: the gaps between
successive deliveries of tokens to a request (a step that commits k
tokens is one delivery), over every gap that lies in the window."""

from harness.stats import percentile


def samples(run):
    out = []
    for r in run.requests.values():
        ts = [t for t, _ in r.deliveries]
        out += [(b - a) * 1e3 for a, b in zip(ts, ts[1:])
                if run.in_window(a) and run.in_window(b)]
    return out


def read(run):
    return percentile(samples(run), 90)
