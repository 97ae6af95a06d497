"""The comparison that decides ``correct``.

After the window closes and the program's state is freed, a sample of the
requests the timed path finished, drawn from the seed with the longest one
in it, is run through the float32 reference that the configuration's
architecture module gives (``bench/arch/<architecture>.py``) over each
prompt followed by its served tokens.  The number compared is the
widest gap by which a served token's logit lies below the reference's best
at that position (greedy serving: a correct server's gap is rounding only).
Every finished request must also have been served exactly its token budget
(the traffic sets no stop token).

The gap numbers compared are those that ``bench/limits/<cell>.json`` sets a
limit for (the widest gap, or the mean gap over the sampled tokens where the
widest does not separate the program from its control); the file records
the readings each limit was set from.
"""

from __future__ import annotations

from bench import arch, traffic

#: served tokens the sample reaches before it stops adding requests
SAMPLE_TOKENS = 512
SAMPLE_MAX_REQUESTS = 8


def sample(records, seed: int) -> list:
    """Finished requests to check: the longest (prompt plus output), then
    others in an order drawn from the seed, until ``SAMPLE_TOKENS`` served
    tokens or ``SAMPLE_MAX_REQUESTS`` requests."""
    done = [r for r in records if r.done is not None and r.n_out > 0]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + r.n_out, -r.rid))
    rest = [r for r in done if r is not longest]
    order = traffic.rng(seed, 5).permutation(len(rest))
    picked, n = [longest], longest.n_out
    for i in order:
        if n >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX_REQUESTS:
            break
        picked.append(rest[i])
        n += rest[i].n_out
    return picked


def served_gaps(ref, picked, bits) -> list[float]:
    """The gap of every served token of ``picked`` under ``ref``."""
    out = []
    for r in picked:
        toks = r.prompt + r.request.out[:-1]
        p = len(r.prompt)
        rows = list(range(p - 1, p - 1 + len(r.request.out)))
        gap, _ = ref.score(toks, rows, r.request.out, bits)
        out += [float(g) for g in gap]
    return out


def verify(win, model: dict, seed: int, limits: dict) -> dict:
    """The checks of one run: ``{name: {"value": v, "limit": l}}``.  A
    missing limit is ``None`` and fails."""
    import jax

    Reference = arch.load(model).Reference
    bits = 8 if model["activations"].startswith("int8") else None
    finished = [r for r in win.records if r.done is not None]
    wrong = sum(1 for r in finished
                if len(r.request.out) != r.budget or r.n_out != r.budget)
    picked = sample(win.records, seed)
    gaps = []
    if picked:
        with jax.default_matmul_precision("highest"):
            ref = Reference(model, seed)
            gaps = served_gaps(ref, picked, bits)
            del ref
    return judge(gaps, wrong, limits)


def judge(gaps: list[float], wrong: int, limits: dict) -> dict:
    """The checks of a set of served-token gaps: ``{name: {"value": v,
    "limit": l}}``.  Each gap number that ``limits`` sets a limit for is
    compared; before any is set, both are reported with the limit ``None``,
    which fails."""
    values = {"max_logit_gap": max(gaps) if gaps else None,
              "mean_logit_gap": sum(gaps) / len(gaps) if gaps else None}
    checks = {"wrong_lengths": {"value": wrong, "limit": 0}}
    for name, value in values.items():
        if name in limits:
            checks[name] = {"value": value, "limit": limits[name]["limit"]}
    if len(checks) == 1:  # no limit set yet: the run cannot be correct
        for name, value in values.items():
            checks[name] = {"value": value, "limit": None}
    return checks


def passed(checks: dict) -> bool:
    return all(c["value"] is not None and c["limit"] is not None
               and c["value"] <= c["limit"] for c in checks.values())
