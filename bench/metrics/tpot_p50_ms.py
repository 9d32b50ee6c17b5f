"""Time per output token, median over requests, in ms.  Per request:
the time from its first token in the window to its last token in the
window, over the tokens after the first; requests with at least two
tokens in the window count."""

from harness.stats import percentile


def samples(run):
    out = []
    for r in run.requests.values():
        d = [(t, k) for t, k in r.deliveries if run.in_window(t)]
        n = sum(k for _, k in d)
        if n >= 2:
            out.append((d[-1][0] - d[0][0]) * 1e3 / (n - 1))
    return out


def read(run):
    return percentile(samples(run), 50)
