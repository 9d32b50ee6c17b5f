"""Output tokens per second: every token delivered in the window, over
the window's seconds."""


def read(run):
    n = sum(k for r in run.requests.values() for t, k in r.deliveries
            if run.in_window(t))
    return n / run.window_s if n else None
