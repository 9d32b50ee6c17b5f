"""Share of its roofline that packed verification reaches, in %: the
least time the chip needs for the verify passes traced (the work the
target's architecture counts for each traced step, ``verify_work``: for
a dense target its weights on the chip, the KV cells of the verified
rows and the logits written, at the peak bandwidth; or their FLOPs at
the peak rate, whichever bounds) over their device time."""

from harness import flops


def read(run):
    t = run.trace
    if t is None or not t.kind_s.get("verify"):
        return None
    steps = run.traced_steps()
    if not steps:
        return None
    least = sum(flops.verify_least_s(run.target, run.peak, c) for c in steps)
    return 100.0 * least / t.kind_s["verify"]
