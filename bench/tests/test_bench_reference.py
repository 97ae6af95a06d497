"""The float32 reference against the served engine, at a small size on the
CPU: the same weights from the seed, and served greedy tokens within
rounding of the reference's best (bf16 and int8 activations).  The controls
(the program's int8 path against the bf16 reference, and int4 activations
against the int8 reference) read far wider gaps."""

import ast
from pathlib import Path

import numpy as np
import pytest

from bench import arch
from bench.arch.bitnet import Reference

MODEL = {"hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 4,
         "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 32,
         "vocab_size": 512, "hidden_act": "silu", "rope_theta": 10000.0,
         "rms_norm_eps": 1e-06, "tie_word_embeddings": False}
PAIRS = {"q": ("attn", "wq"), "k": ("attn", "wk"), "v": ("attn", "wv"),
         "o": ("attn", "wo"), "up": ("ffn", "wi"), "gate": ("ffn", "wg"),
         "down": ("ffn", "wo")}


def program_cfg(act):
    from repro.configs.registry import get_config
    from repro.models.config import reduced

    return reduced(get_config("bitnet-b1.58-2b")).with_(act_dtype=act)


def serve(act, seed):
    from repro.launch.serve import load_served
    from repro.serving.engine import DecodeEngine, Request
    from repro.serving.scheduler import ContinuousScheduler

    cfg = program_cfg(act)
    engine = DecodeEngine(load_served(cfg, seed=seed), cfg, batch_size=4,
                          max_len=160)
    rng = np.random.default_rng(seed % 1000)
    reqs = [Request(prompt=rng.integers(2, 512, size=n).tolist(),
                    max_new_tokens=m)
            for n, m in [(40, 24), (70, 16), (5, 30), (100, 12), (33, 20)]]
    sched = ContinuousScheduler(engine)
    for r in reqs:
        sched.submit(r)
    sched.run()
    return reqs


def widest(ref, reqs, bits, argmax_of=None):
    """Widest gap of the served tokens under ``ref``; with ``argmax_of``
    (bits of a second reference pass), of the tokens that pass puts first
    at the same positions instead."""
    out = 0.0
    for r in reqs:
        toks = r.prompt + r.out[:-1]
        p = len(r.prompt)
        rows = list(range(p - 1, p - 1 + len(r.out)))
        targets = r.out
        if argmax_of is not None:
            _, targets = ref.score(toks, rows, r.out, argmax_of)
            targets = targets.tolist()
        gap, _ = ref.score(toks, rows, targets, bits)
        out = max(out, float(gap.max()))
    return out


#: what a reference may import: the standard library's few, jax and numpy,
#: and of the benchmark only its shared work rules
ALLOWED = {"__future__", "functools", "math", "jax", "numpy", "bench.work"}


def imported(src: str):
    """Each name that ``src`` imports, with its module's dotted path."""
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield from (f"{node.module}.{a.name}" for a in node.names)


@pytest.mark.parametrize("name", arch.names())
def test_the_reference_imports_nothing_of_the_program(name):
    src = (Path(arch.__file__).parent / f"{name}.py").read_text()
    bad = [m for m in imported(src)
           if not any(m == a or m.startswith(a + ".") for a in ALLOWED)]
    assert not bad


@pytest.mark.parametrize("seed", [0, 2**31 + 77])
def test_the_reference_draws_the_served_weights(seed):
    from repro.core.encoding import unpack_base3
    from repro.launch.serve import load_served

    served = load_served(program_cfg("none"), seed=seed)
    ref = Reference(MODEL, seed)
    for name, (blk, leaf) in PAIRS.items():
        t, s = ref.trits(name)
        packed = served["blocks"][blk][leaf]
        got = np.swapaxes(np.asarray(unpack_base3(packed["packed"],
                                                  t.shape[1])), -1, -2)
        np.testing.assert_array_equal(got, t)
        np.testing.assert_array_equal(
            np.asarray(packed["scale"], np.float32), s)
    np.testing.assert_array_equal(np.asarray(served["embed"]["w"]),
                                  np.asarray(ref.w["embed"]))
    np.testing.assert_array_equal(np.asarray(served["lm_head"]["w"]),
                                  np.asarray(ref.w["head"]))


@pytest.mark.parametrize("act,bits", [("none", None), ("int8", 8)])
def test_served_tokens_are_the_reference_best_to_rounding(act, bits):
    seed = 5
    gap = widest(Reference(MODEL, seed), serve(act, seed), bits)
    assert gap < 0.06


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_controls_read_wider_gaps(seed):
    ref = Reference(MODEL, seed)
    served_int8 = serve("int8", seed)
    bf16 = widest(ref, serve("none", seed), None)
    int8 = widest(ref, served_int8, 8)
    # the bf16 configuration's control: the program's own int8 path
    assert widest(ref, served_int8, None) > bf16
    # the int8 configuration's control: int4 activations in the reference
    assert widest(ref, served_int8, 8, argmax_of=4) > 5 * int8
