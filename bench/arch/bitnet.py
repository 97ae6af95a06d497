"""bitnet-b1.58: its plain float32 reference, the keys the program is held
to, and its work counts.

The reference imports nothing of the program under test.  It reads the
sizes from the configuration file's keys and draws the same master weights
from ``jax.random.PRNGKey(seed)`` in the layout the served checkpoint uses:

- ``split(key, 16)``: 0 embedding ``[V, d]`` (normal * 0.02), 1 LM head
  ``[d, V]`` (normal / sqrt d; absent when tied), then the stacked attention
  and FFN leaves, each ``[L, d_in, d_out]`` bf16 normal / sqrt(d_in): attention
  from ``split(k2, 4)`` (q, k, v, o), FFN from ``split(k3, 3)`` (up, gate,
  down); ``V`` is the vocabulary rounded up to a multiple of 256; RMSNorm
  gains are 1.
- BitNet b1.58 absmean ternarization of each layer's projection, on the bf16
  master weights: ``W_t = clip(round(W / mean|W|), -1, 1)``.  Ternarization is
  not continuous, so it is computed in the master weights' own precision.

The forward pass is float32 with ``Precision.HIGHEST`` matmuls: RMSNorm,
rotary embedding (halves rotated), causal grouped-query attention, gated
FFN, untied or tied LM head.  With ``act_bits`` set, every ternary
projection first quantizes its input per token to signed ``act_bits``
integers by absmax (BitNet b1.58's W1.58A8 at 8 bits).

The work counts take every layer as seven dense ternary projections (q, k,
v, o, gate, up, down) and attention as ``QK^T`` plus ``PV`` over the live
context, on the shared rules of ``bench/work.py``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import work

HI = jax.lax.Precision.HIGHEST
EPS_SCALE = 1e-6
#: ``score`` pads the sequence and the rows it reads to multiples of these,
#: so that every request of a chat mix (at most 2048 positions) shares one
#: compiled program
SEQ_BLOCK = 2048
ROW_BLOCK = 1024
ACTS = {"silu": jax.nn.silu}

#: configuration file key -> the program's ``ModelConfig`` attribute, each
#: held equal by ``harness.program_config``
PROGRAM_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
                "num_hidden_layers": "n_layers",
                "num_attention_heads": "n_heads",
                "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
                "vocab_size": "vocab_size", "rope_theta": "rope_theta",
                "hidden_act": "act_fn", "tie_word_embeddings": "tie_embeddings",
                "torch_dtype": "dtype"}


def sizes(model: dict) -> dict:
    """The numbers the reference needs, from a configuration file's keys."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    return dict(L=model["num_hidden_layers"], d=d, H=h,
                Hkv=model["num_key_value_heads"],
                hd=model.get("head_dim") or d // h,
                ff=model["intermediate_size"], vocab=model["vocab_size"],
                theta=float(model["rope_theta"]),
                eps=float(model["rms_norm_eps"]), act=model["hidden_act"],
                tied=bool(model["tie_word_embeddings"]))


def _ternary(w):
    s = jnp.clip(jnp.mean(jnp.abs(w), axis=(-2, -1), keepdims=True),
                 EPS_SCALE, None)
    t = jnp.clip(jnp.round(w / s), -1, 1).astype(jnp.int8)
    return t, s[..., 0, 0].astype(jnp.bfloat16).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0,))
def _weights(key_sizes: tuple, key):
    z = dict(key_sizes)
    L, d, H, Hkv, hd, ff = z["L"], z["d"], z["H"], z["Hkv"], z["hd"], z["ff"]
    V = -(-z["vocab"] // 256) * 256
    bf = jnp.bfloat16
    ks = jax.random.split(key, 16)
    normal = jax.random.normal
    w = {"embed": normal(ks[0], (V, d), bf) * 0.02}
    i = 1
    if not z["tied"]:
        w["head"] = normal(ks[1], (d, V), bf) * (1.0 / math.sqrt(d))
        i = 2
    a = jax.random.split(ks[i], 4)
    f = jax.random.split(ks[i + 1], 3)

    def lin(k, din, dout):
        return _ternary(normal(k, (L, din, dout), bf) * (1.0 / math.sqrt(din)))

    for name, k, din, dout in (("q", a[0], d, H * hd), ("k", a[1], d, Hkv * hd),
                               ("v", a[2], d, Hkv * hd), ("o", a[3], H * hd, d),
                               ("up", f[0], d, ff), ("gate", f[1], d, ff),
                               ("down", f[2], ff, d)):
        w[name] = lin(k, din, dout)
    return w


class Reference:
    """The reference model of one configuration and seed."""

    def __init__(self, model: dict, seed: int):
        self.z = sizes(model)
        if self.z["act"] not in ACTS:
            raise ValueError(f"reference has no activation {self.z['act']!r}")
        self.w = _weights(tuple(sorted(self.z.items())),
                          jax.random.PRNGKey(seed))

    def trits(self, name: str) -> tuple[np.ndarray, np.ndarray]:
        """The ``[L, d_in, d_out]`` trits and ``[L]`` scales of a projection."""
        t, s = self.w[name]
        return np.asarray(t), np.asarray(s)

    def score(self, tokens: list[int], rows: list[int], targets: list[int],
              act_bits: int | None) -> tuple[np.ndarray, np.ndarray]:
        """Run ``tokens`` and, at each of ``rows``, return the gap by which
        the target's logit lies below the best one, and the best token."""
        S = -(-len(tokens) // SEQ_BLOCK) * SEQ_BLOCK
        R = -(-len(rows) // ROW_BLOCK) * ROW_BLOCK
        tok = np.zeros(S, np.int32)
        tok[:len(tokens)] = tokens
        r = np.zeros(R, np.int32)
        r[:len(rows)] = rows
        tg = np.zeros(R, np.int32)
        tg[:len(targets)] = targets
        gap, best = _score(tuple(sorted(self.z.items())), act_bits, self.w,
                           jnp.asarray(tok), jnp.asarray(r), jnp.asarray(tg))
        return np.asarray(gap)[:len(rows)], np.asarray(best)[:len(rows)]


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    S, _, hd = x.shape
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _proj(x, t, s, act_bits):
    wf = t.astype(jnp.float32)
    if act_bits is None:
        return jnp.matmul(x, wf, precision=HI) * s
    qmax = float(2 ** (act_bits - 1) - 1)
    xs = jnp.clip(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-6,
                  None) / qmax
    xq = jnp.clip(jnp.round(x / xs), -qmax, qmax)
    return jnp.matmul(xq, wf, precision=HI) * (s * xs)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _score(key_sizes, act_bits, w, tokens, rows, targets):
    z = dict(key_sizes)
    S = tokens.shape[0]
    H, Hkv, hd, eps = z["H"], z["Hkv"], z["hd"], z["eps"]
    rep = H // Hkv
    act = ACTS[z["act"]]
    causal = jnp.tril(jnp.ones((S, S), bool))
    h = w["embed"][tokens].astype(jnp.float32)

    def layer(h, lw):
        x = _rms(h, eps)
        q = _proj(x, *lw["q"], act_bits).reshape(S, H, hd)
        k = _proj(x, *lw["k"], act_bits).reshape(S, Hkv, hd)
        v = _proj(x, *lw["v"], act_bits).reshape(S, Hkv, hd)
        q, k = _rope(q, z["theta"]), _rope(k, z["theta"])
        q = q.reshape(S, Hkv, rep, hd)
        s = jnp.einsum("qgrd,kgd->grqk", q, k, precision=HI) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI).reshape(S, H * hd)
        h = h + _proj(o, *lw["o"], act_bits)
        x = _rms(h, eps)
        g = act(_proj(x, *lw["gate"], act_bits)) * _proj(x, *lw["up"],
                                                          act_bits)
        return h + _proj(g, *lw["down"], act_bits), None

    names = ("q", "k", "v", "o", "up", "gate", "down")
    h, _ = jax.lax.scan(layer, h, {n: w[n] for n in names})
    x = _rms(h[rows], eps)
    head = w["embed"].T if z["tied"] else w["head"]
    logits = jnp.matmul(x, head.astype(jnp.float32), precision=HI)
    logits = jnp.where(jnp.arange(logits.shape[-1]) < z["vocab"], logits,
                       -jnp.inf)
    best = jnp.argmax(logits, axis=-1)
    gap = jnp.max(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[:, None], axis=-1)[:, 0]
    return gap, best


def projections(model: dict) -> list[tuple[str, int, int]]:
    """``(role, K, N)`` of each ternary projection of one layer."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    hd = model.get("head_dim") or d // h
    kv = model["num_key_value_heads"] * hd
    ff = model["intermediate_size"]
    return [("q", d, h * hd), ("k", d, kv), ("v", d, kv), ("o", h * hd, d),
            ("gate", d, ff), ("up", d, ff), ("down", ff, d)]


def least_seconds(m: int, model: dict, act_bytes: int, peak_flops: float,
                  hbm: float) -> float:
    """Least time for every ternary projection of one forward over ``m``
    rows: per projection the larger of its compute and its memory bound."""
    per_layer = 0.0
    for _, k, n in projections(model):
        flops, nbytes = work.projection_work(m, k, n, act_bytes)
        per_layer += max(flops / peak_flops, nbytes / hbm)
    return per_layer * model["num_hidden_layers"]


def ternary_params(model: dict) -> int:
    return model["num_hidden_layers"] * sum(k * n for _, k, n in
                                            projections(model))


def attention_flops(model: dict, context: int) -> float:
    """``QK^T`` and ``PV`` of one token over ``context`` positions."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    hd = model.get("head_dim") or d // h
    return 4.0 * model["num_hidden_layers"] * h * hd * context


def token_flops(model: dict, context: int, head: bool) -> float:
    """Model FLOPs of one token at ``context`` positions (itself included),
    with the LM head where that token's logits are needed."""
    return 2.0 * ternary_params(model) + attention_flops(model, context) + \
        (work.head_flops(model) if head else 0.0)
