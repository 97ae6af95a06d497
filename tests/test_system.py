"""End-to-end system behaviour: the fault-tolerant train loop (losses
decrease, checkpoint/restart resumes bit-continuously), and the full
train → quantize → serve lifecycle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.launch.mesh import make_mesh_for
from repro.launch.train import train
from repro.models.decode import quantize_for_serving
from repro.serving.engine import DecodeEngine, Request


def _mesh():
    return make_mesh_for(1)


def test_train_loss_decreases():
    cfg = get_smoke_config("bitnet-b1.58-2b").with_(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=256, loss_chunk=32)
    out = train(cfg, steps=25, global_batch=4, seq_len=64, mesh=_mesh(),
                lr=3e-3, log_every=100)
    hist = out["history"]
    assert out["exit"] == "done"
    assert np.mean(hist[-5:]) < np.mean(hist[:5]) - 0.1, hist


def test_checkpoint_restart_resumes(tmp_path):
    cfg = get_smoke_config("qwen3-0.6b").with_(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=256, loss_chunk=32)
    kw = dict(global_batch=4, seq_len=32, mesh=_mesh(),
              ckpt_dir=str(tmp_path), checkpoint_every=5, log_every=100)
    # run 10 steps straight through
    full = train(cfg, steps=10, **kw)
    # fresh dir: run 5, "crash", resume to 10
    import shutil
    shutil.rmtree(tmp_path)
    train(cfg, steps=5, **kw)
    resumed = train(cfg, steps=10, **kw)
    # deterministic data + restored state ⇒ identical trailing losses
    np.testing.assert_allclose(resumed["history"][-3:], full["history"][-3:],
                               rtol=1e-4, atol=1e-4)


def test_full_lifecycle_train_quantize_serve(key):
    cfg = get_smoke_config("bitnet-b1.58-2b").with_(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=256, loss_chunk=32)
    out = train(cfg, steps=5, global_batch=4, seq_len=32, mesh=_mesh(),
                log_every=100)
    sp = quantize_for_serving(out["params"], cfg)
    eng = DecodeEngine(sp, cfg, batch_size=2, max_len=48)
    reqs = eng.run([Request(prompt=[3, 4, 5], max_new_tokens=5)])
    assert len(reqs[0].out) == 5


def test_grad_compression_path_trains():
    cfg = get_smoke_config("qwen3-0.6b").with_(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=256, loss_chunk=32)
    out = train(cfg, steps=10, global_batch=4, seq_len=32, mesh=_mesh(),
                compress_grads=True, lr=3e-3, log_every=100)
    assert out["exit"] == "done"
    assert np.isfinite(out["history"]).all()
