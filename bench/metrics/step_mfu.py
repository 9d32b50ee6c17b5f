"""Model FLOP utilisation of the whole engine, in %: the target's FLOPs
for every prompt prefilled in the window (the step that prefills a
prompt delivers its first token) and every token its steps committed in
the window, as the target's architecture counts them (``prefill_flops``,
``token_flops``: for a dense target 2 x its matmul parameters per token,
plus attention over the context), over the summed host time of the
engine's calls in the window times the chip's peak FLOP/s."""


def read(run):
    busy = sum(c.end - c.start for c in run.calls if run.in_window(c.start))
    if busy <= 0:
        return None
    arch, m = run.target.arch, run.target.sizes
    work = 0.0
    for r in run.requests.values():
        if r.first_token is not None and run.in_window(r.first_token):
            work += arch.prefill_flops(m, r.prompt_len)
        ctx = r.prompt_len
        for i, (t, k) in enumerate(r.deliveries):
            if i and run.in_window(t):
                work += sum(arch.token_flops(m, ctx + j) for j in range(k))
            ctx += k
    return 100.0 * work / (busy * run.peak["bf16_flops"])
