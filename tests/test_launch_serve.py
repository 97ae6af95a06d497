"""The serving launcher's shared path (``repro.launch.serve``: load, serve,
device report), the compile-cache helper, and ``chip_smoke.py``'s refusal to
run anywhere but on a TPU inside the checkout."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.kernels.dispatch import select_kernel
from repro.launch import compile_cache
from repro.launch.serve import describe_devices, load_served, serve_requests
from repro.models.decode import quantize_for_serving
from repro.models.model import init_params
from repro.serving.engine import DecodeEngine, Request

ROOT = Path(__file__).resolve().parents[1]


def _cfg():
    return get_smoke_config("bitnet-b1.58-2b").with_(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=256, loss_chunk=32)


def _engine(cfg, **kw):
    return DecodeEngine(load_served(cfg), cfg, batch_size=2, max_len=48,
                        prefill_chunk=32, **kw)


def test_load_served_is_the_quantized_seeded_init():
    """One jitted program, the same artifact as init + quantize op by op."""
    cfg = _cfg()
    got = load_served(cfg, seed=3)
    want = quantize_for_serving(init_params(cfg, jax.random.PRNGKey(3)), cfg)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serve_requests_disciplines_agree():
    cfg = _cfg()
    eng = _engine(cfg, matmul_policy="fixed:ref")
    outs = {}
    for discipline in ("continuous", "generational"):
        reqs = [Request(prompt=[3 + i, 5, 7], max_new_tokens=4)
                for i in range(2)]
        steps, sched = serve_requests(eng, reqs, discipline=discipline)
        assert (sched is None) == (discipline == "generational")
        assert steps >= 4
        outs[discipline] = [r.out for r in reqs]
    assert all(len(o) == 4 for o in outs["continuous"])
    assert outs["continuous"] == outs["generational"]


def test_kernel_choices_resolve_like_dispatch():
    cfg = _cfg()
    choices = _engine(cfg).kernel_choices()
    assert {"sched_step", "prefill_chunk"} <= {e for e, *_ in choices}
    for (_, role, m, k, n), name in choices.items():
        assert name == select_kernel(m, k, n, cfg.dtype,
                                     policy=cfg.matmul_policy,
                                     role=role).name
    pinned = _engine(cfg, matmul_policy="fixed:ref").kernel_choices()
    assert pinned.keys() == choices.keys()
    assert set(pinned.values()) == {"ref"}


def test_describe_devices_reports_jax():
    assert describe_devices() == {"platform": jax.devices()[0].platform,
                                  "kind": jax.devices()[0].device_kind,
                                  "count": len(jax.devices())}


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    want = str(ROOT / ".jax_cache")
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu_or_repo(where, tmp_path):
    """On the CPU, and as a lone copy outside the checkout, the script exits
    non-zero and prints no result line."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           compile_cache.ENV: str(tmp_path / "cache")}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0, proc.stdout
    assert '"ok"' not in proc.stdout
