"""Whole step: model FLOPs of the tokens the traced stretch processed, over
the stretch times the chip's peak (bf16, or int8 under int8 activations).

Decode: each token that became visible in the stretch counts one forward
at its context, LM head included.  Prefill: the admission chunks that
ended in the stretch, matched to requests in admission order
(``SchedulerStats.queue_wait_by_rid`` keeps it), each count its valid
prompt tokens at their positions, and the last chunk of a prompt its LM
head row.  A token's FLOPs are ``token_flops`` of the configuration's
architecture module (``bench/arch/<architecture>.py``)."""

from bench import arch, work


def _chunk_flops(token_flops, model, start, valid, last):
    f = sum(token_flops(model, p + 1, head=False)
            for p in range(start, start + valid))
    return f + (work.head_flops(model) if last else 0.0)


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    model = ctx.cell.model
    token_flops = arch.load(model).token_flops
    win = ctx.win
    lo, hi = t.t_start, t.t_stop
    flops = 0.0
    for r in win.records:
        p = len(r.prompt)
        for k, when in enumerate(r.times):
            if lo < when <= hi:
                flops += token_flops(model, p + k + 1, head=True)
    C = ctx.prefill_chunk
    by_rid = {r.rid: r for r in win.records}
    chunks = [s for s in win.spans if s.name == "sched_admit_step"]
    i = 0
    for rid in win.stats.queue_wait_by_rid:
        r = by_rid.get(rid)
        if r is None:
            continue
        p = len(r.prompt)
        n = -(-p // C)
        for j in range(n):
            if i >= len(chunks):
                break
            if lo < chunks[i].t1 <= hi:
                valid = min(C, p - j * C)
                flops += _chunk_flops(token_flops, model, j * C, valid,
                                      j == n - 1)
            i += 1
    pk = work.peaks(ctx.device_kind)
    int8 = model["activations"].startswith("int8")
    peak = pk["int8_ops"] if int8 else pk["bf16_flops"]
    if not flops:
        return None
    return 100.0 * flops / (t.window_s * peak)
