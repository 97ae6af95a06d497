"""Fault-tolerant distributed training loop.

Production behaviors implemented (and exercised on local devices by tests
and examples — the same code path drives the 512-chip mesh):

  * **checkpoint/restart** — async atomic checkpoints every
    ``--checkpoint-every`` steps; ``--resume auto`` restores the latest
    committed step (crc-validated) and the data stream realigns to it
    deterministically (the pipeline is a pure function of step).
  * **elastic restarts** — checkpoints store logical (unsharded) arrays;
    on restore they are device_put against the *current* mesh's shardings,
    so a restart may change pod/host count.
  * **straggler mitigation** — per-step deadline watchdog: a step exceeding
    ``deadline_factor ×`` the trailing-median step time is logged and
    counted; after ``max_straggler_strikes`` the loop checkpoints and exits
    non-zero so the scheduler can reschedule around the slow host (on real
    pods the signal keys off the cross-host step barrier; here the timing
    harness is identical with the barrier replaced by device sync).
  * **gradient compression** — optional int8 error-feedback all-reduce
    (``--compress-grads``), see optim/compression.py.
  * **NaN containment** — non-finite loss skips the update (grad-skip), a
    standard guard for QAT at scale.

On multi-host TPU this file is launched per host (jax.distributed handles
process groups); the container runs it single-process on CPU devices.
"""

from __future__ import annotations

import argparse
import functools
import statistics
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import checkpointing as ckpt
from repro.configs.registry import get_config, get_smoke_config
from repro.data.pipeline import DataConfig, SyntheticLMStream
from repro.launch.mesh import make_mesh_for
from repro.models.model import init_params, train_loss
from repro.optim import compression
from repro.optim.optimizers import clip_by_global_norm, make_optimizer
from repro.parallel import sharding as sh


def make_train_step(cfg, opt, compress: bool = False):
    def train_step(params, opt_state, err_state, batch, step):
        (loss, metrics), grads = jax.value_and_grad(
            train_loss, has_aux=True)(params, cfg, batch)
        if compress:
            grads, err_state = compression.roundtrip(grads, err_state)
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        new_params, new_state = opt.update(opt_state, grads, params, step)
        # NaN containment: skip the update when loss/grads blow up.
        ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
        new_params = jax.tree.map(lambda n, o: jnp.where(ok, n, o), new_params, params)
        new_state = jax.tree.map(lambda n, o: jnp.where(ok, n, o), new_state, opt_state)
        return new_params, new_state, err_state, {
            "loss": loss, "gnorm": gnorm, "skipped": (~ok).astype(jnp.float32),
            **metrics}
    return train_step


def train(cfg, *, steps: int, global_batch: int, seq_len: int, mesh,
          ckpt_dir: str | None = None, checkpoint_every: int = 50,
          resume: str = "auto", compress_grads: bool = False,
          deadline_factor: float = 3.0, max_straggler_strikes: int = 5,
          log_every: int = 10, lr: float = 3e-4):
    key = jax.random.PRNGKey(0)
    opt = make_optimizer(cfg.optimizer, base_lr=lr, total=steps)

    params_sds = jax.eval_shape(functools.partial(init_params, cfg), key)
    pspecs = sh.param_specs(params_sds, mesh)
    psh = sh.to_shardings(pspecs, mesh)
    sspecs = opt.state_specs(pspecs, params_sds)
    ssh = sh.to_shardings(sspecs, mesh)

    with mesh:
        params = jax.jit(functools.partial(init_params, cfg),
                         out_shardings=psh)(key)
        opt_state = jax.jit(opt.init, out_shardings=ssh)(params)
    err_state = compression.init_error_state(params) if compress_grads else {}

    start_step = 0
    if ckpt_dir and resume == "auto":
        latest = ckpt.latest_step(ckpt_dir)
        if latest is not None:
            state = ckpt.restore(ckpt_dir, latest,
                                 {"params": params_sds,
                                  "opt": jax.eval_shape(opt.init, params_sds)})
            # elastic: re-place against the *current* mesh
            params = jax.device_put(state["params"], psh)
            opt_state = jax.device_put(state["opt"], ssh)
            start_step = latest + 1
            print(f"[train] resumed from step {latest}")

    data = SyntheticLMStream(DataConfig(cfg.vocab_size, seq_len, global_batch))
    sample = data.batch(0)
    bsh = sh.to_shardings(sh.batch_specs(sample, mesh), mesh)

    step_fn = jax.jit(make_train_step(cfg, opt, compress_grads),
                      in_shardings=(psh, ssh, None, bsh, NamedSharding(mesh, P())),
                      out_shardings=(psh, ssh, None, None),
                      donate_argnums=(0, 1, 2))

    times, strikes = [], 0
    history = []
    for step in range(start_step, steps):
        batch = jax.device_put(data.batch(step), bsh)
        t0 = time.time()
        with mesh:
            params, opt_state, err_state, metrics = step_fn(
                params, opt_state, err_state, batch, jnp.asarray(step))
        jax.block_until_ready(metrics["loss"])
        dt = time.time() - t0
        # --- straggler watchdog ---
        if len(times) >= 5:
            med = statistics.median(times[-20:])
            if dt > deadline_factor * med:
                strikes += 1
                print(f"[train] straggler: step {step} took {dt:.2f}s "
                      f"(median {med:.2f}s) — strike {strikes}")
                if strikes >= max_straggler_strikes:
                    if ckpt_dir:
                        ckpt.save(ckpt_dir, step,
                                  {"params": params, "opt": opt_state})
                    print("[train] too many stragglers; checkpointed, "
                          "exiting for reschedule")
                    return {"exit": "straggler", "step": step,
                            "history": history}
        times.append(dt)
        history.append(float(metrics["loss"]))
        if step % log_every == 0 or step == steps - 1:
            print(f"[train] step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['gnorm']):.3f} {dt*1e3:.0f}ms"
                  + (" SKIPPED" if float(metrics['skipped']) else ""))
        if ckpt_dir and (step + 1) % checkpoint_every == 0:
            ckpt.save_async(ckpt_dir, step, {"params": params, "opt": opt_state})
    ckpt.wait()
    if ckpt_dir:
        ckpt.save(ckpt_dir, steps - 1, {"params": params, "opt": opt_state})
    return {"exit": "done", "step": steps - 1, "history": history,
            "params": params}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--resume", default="auto", choices=["auto", "none"])
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_mesh_for(jax.device_count())
    out = train(cfg, steps=args.steps, global_batch=args.global_batch,
                seq_len=args.seq_len, mesh=mesh, ckpt_dir=args.ckpt_dir,
                checkpoint_every=args.checkpoint_every, resume=args.resume,
                compress_grads=args.compress_grads, lr=args.lr)
    sys.exit(0 if out["exit"] == "done" else 17)


if __name__ == "__main__":
    main()
