"""Median host time of one ``SpinEngine.step()`` call in the window, in
ms.  The step ends in host syncs on the draft candidates and the verify
outputs, so the host clock around it covers its device work."""

from harness.stats import percentile


def read(run):
    return percentile([(c.end - c.start) * 1e3 for c in run.calls
                       if c.kind == "step" and run.in_window(c.start)], 50)
