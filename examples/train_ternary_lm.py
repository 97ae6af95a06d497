"""End-to-end QAT training driver: BitNet b1.58-style ternary training with
the fault-tolerant loop (checkpoint/resume, straggler watchdog, optional
gradient compression), then conversion to the packed serving artifact.

Run:  PYTHONPATH=src python examples/train_ternary_lm.py \
          [--arch bitnet-b1.58-2b] [--steps 200] [--dim 256] [--layers 4]

The default is a ~10M-parameter reduction that trains in minutes on CPU; on
a pod, drop --dim/--layers to use the full config with the production mesh.
"""

import argparse

import jax

from repro.configs.registry import get_config
from repro.launch.mesh import make_mesh_for
from repro.launch.train import train
from repro.models.config import reduced
from repro.models.decode import packed_bits_per_weight, quantize_for_serving


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bitnet-b1.58-2b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/ternary_lm_ckpt")
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()

    cfg = reduced(get_config(args.arch), d_model=args.dim, n_layers=args.layers,
                  n_heads=max(args.dim // 64, 1),
                  n_kv_heads=max(args.dim // 128, 1),
                  head_dim=64, d_ff=args.dim * 4, vocab_size=4096,
                  loss_chunk=128)
    print(f"[train] {cfg.name} reduced to {cfg.param_count()/1e6:.1f}M params; "
          f"QAT with STE ternary weights")

    mesh = make_mesh_for(jax.device_count())
    out = train(cfg, steps=args.steps, global_batch=args.global_batch,
                seq_len=args.seq_len, mesh=mesh, ckpt_dir=args.ckpt_dir,
                checkpoint_every=50, compress_grads=args.compress_grads,
                lr=1e-3, log_every=20)
    h = out["history"]
    print(f"[train] loss {h[0]:.3f} -> {h[-1]:.3f} over {len(h)} steps "
          f"({out['exit']})")

    served = quantize_for_serving(out["params"], cfg)
    print(f"[train] serving artifact: {packed_bits_per_weight(served):.3f} "
          f"bits/weight — ready for examples/serve_ternary.py")


if __name__ == "__main__":
    main()
