"""A whole benchmark run at a small size on the CPU, with the look for a chip
skipped: the result line's shape, ``correct`` on the sound program, and
``correct`` false with the timed path broken underneath."""

import json

import numpy as np
import pytest

from bench import harness

TINY = {
    "name": "tiny", "architecture": "bitnet", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 1, "head_dim": 32, "vocab_size": 512,
    "hidden_act": "silu", "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "activations": "bfloat16",
    "serving": {"arch": "bitnet-b1.58-2b", "act_dtype": "none"},
}
MIX = {"name": "tinychat", "loop": "open",
       "arrivals": {"kind": "poisson", "rate_per_s": 3.0},
       "prompt_tokens": {"kind": "lognormal", "median": 24, "sigma": 0.8,
                         "min": 4, "max": 90},
       "output_tokens": {"kind": "lognormal", "median": 10, "sigma": 0.5,
                         "min": 4, "max": 30},
       "slots": 4, "max_len": 128, "warmup_s": 1.0}
#: the widest gap of the sound program at this size reads about 0.01-0.03
LIMIT = 0.15


@pytest.fixture
def root(tmp_path, monkeypatch):
    from repro.configs.registry import get_config
    from repro.models.config import reduced

    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "limits").mkdir()
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(TINY))
    (tmp_path / "bench" / "traffic" / "tinychat.json").write_text(
        json.dumps(MIX))
    (tmp_path / "bench" / "limits" / "tiny.tinychat.json").write_text(
        json.dumps({"max_logit_gap": {"limit": LIMIT}}))
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "file": "bench/configs/tiny.json"}]
    bench["workloads"] = [{"name": "tiny.tinychat", "config": "tiny",
                           "traffic": "tinychat", "chips": 1}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "program_config", lambda model: reduced(
        get_config("bitnet-b1.58-2b")).with_(
            act_dtype=model["serving"]["act_dtype"]))
    return tmp_path


def run(root, tmp_path, seed=3):
    import time

    return harness.run_cell("tiny.tinychat", seed, 3.0, False,
                            t_start=time.perf_counter(), root=root,
                            require_tpu=False, tmp=tmp_path,
                            compile_cache=False)


def test_the_sound_program_is_correct(root, tmp_path):
    out = run(root, tmp_path)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"served_tok_s", "ttft_p90_s",
                                   "tpot_p90_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["max_logit_gap"]["value"] < LIMIT / 3
    json.dumps(out)


def test_a_run_on_the_cpu_is_refused(root):
    with pytest.raises(harness.NoChip):
        harness.check_devices(1)


def _break(monkeypatch, how):
    """Break the engine's step once set-up has warmed it."""
    import jax
    import jax.numpy as jnp

    build = harness.build

    def broken_build(cfg, seed, mix):
        engine = build(cfg, seed, mix)
        orig = engine.sched_step

        def step(state):
            # the step donates its input: keep a copy of what a fault reuses
            old = jax.tree.map(jnp.copy, state)
            if how == "unchanged":
                _, toks, alive = orig(state)
                return old, toks, np.ones_like(alive)
            new, toks, alive = orig(state)
            toks = np.array(toks)
            if how == "token":
                toks[0] = (toks[0] + 1) % cfg.vocab_size
            elif how == "half":
                # the first half of the slots keep the logits of the last
                # step: their next tokens come from stale work
                half = np.arange(len(toks)) < len(toks) // 2
                new = dict(new, logits=new["logits"].at[half].set(
                    old["logits"][half]))
            return new, toks, alive

        engine.sched_step = step
        return engine

    monkeypatch.setattr(harness, "build", broken_build)


@pytest.mark.parametrize("how", ["token", "unchanged", "half"])
def test_a_broken_timed_path_is_not_correct(root, tmp_path, monkeypatch,
                                            how):
    _break(monkeypatch, how)
    out = run(root, tmp_path)
    assert not out["correct"], out["checks"]
