"""Device time of drafting per engine step, in ms: the drafters'
``decode_paged`` programs (draft tokens and the catch-up decode) in the
traced part of the window, over the steps traced."""


def read(run):
    t = run.trace
    if t is None or t.steps == 0 or "draft" not in t.kind_s:
        return None
    return t.kind_s["draft"] * 1e3 / t.steps
