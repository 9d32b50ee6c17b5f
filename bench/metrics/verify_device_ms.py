"""Device time of packed verification per engine step, in ms: the
target's ``verify_paged`` programs in the traced part of the window,
over the steps traced."""


def read(run):
    t = run.trace
    if t is None or t.steps == 0 or "verify" not in t.kind_s:
        return None
    return t.kind_s["verify"] * 1e3 / t.steps
