"""Sharding-rule invariants for every arch on both production mesh shapes —
checked structurally (no 512-device compile; that's the dry-run's job)."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.registry import ARCHS, input_specs
from repro.configs.shapes import cells_for
from repro.launch.mesh import make_mesh_for
from repro.models.decode import quantize_for_serving
from repro.models.model import init_params
from repro.optim.optimizers import make_optimizer
from repro.parallel import sharding as sh

MESHES = [AbstractMesh((16, 16), ("data", "model")),
          AbstractMesh((2, 16, 16), ("pod", "data", "model"))]


def _check_divisible(tree_sds, tree_specs, mesh):
    leaves = jax.tree.leaves(tree_sds)
    specs = jax.tree.leaves(tree_specs, is_leaf=lambda s: isinstance(s, P))
    assert len(leaves) == len(specs)
    sharded = 0
    for sds, spec in zip(leaves, specs):
        dims = list(spec) + [None] * (sds.ndim - len(spec))
        for size, axes in zip(sds.shape, dims):
            if axes is None:
                continue
            shards = 1
            for a in (axes if isinstance(axes, tuple) else (axes,)):
                shards *= mesh.shape[a]
            assert size % shards == 0, (sds.shape, spec)
            sharded += 1
    return sharded


@pytest.mark.parametrize("mesh", MESHES, ids=["16x16", "2x16x16"])
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_param_specs_divisible(arch, mesh, key):
    cfg = ARCHS[arch]
    sds = jax.eval_shape(functools.partial(init_params, cfg), key)
    specs = sh.param_specs(sds, mesh)
    n = _check_divisible(sds, specs, mesh)
    assert n > 0, "no parameter ended up sharded"


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_packed_specs_divisible(arch, key):
    mesh = MESHES[0]
    cfg = ARCHS[arch]
    sds = jax.eval_shape(functools.partial(init_params, cfg), key)
    packed = jax.eval_shape(functools.partial(quantize_for_serving, cfg=cfg), sds)
    specs = sh.param_specs(packed, mesh)
    assert _check_divisible(packed, specs, mesh) > 0


@pytest.mark.parametrize("mesh", MESHES, ids=["16x16", "2x16x16"])
def test_cell_input_specs_divisible(mesh):
    for arch in sorted(ARCHS):
        if arch == "bitnet-b1.58-2b":
            continue
        for shape_name in cells_for(arch):
            cfg, shape, specs = input_specs(arch, shape_name)
            if shape.kind == "decode":
                _check_divisible(specs["cache"], sh.cache_specs(specs["cache"], mesh), mesh)
            else:
                _check_divisible(specs, sh.batch_specs(specs, mesh), mesh)


def test_opt_state_specs_divisible(key):
    mesh = MESHES[0]
    cfg = ARCHS["qwen2.5-14b"]
    sds = jax.eval_shape(functools.partial(init_params, cfg), key)
    pspecs = sh.param_specs(sds, mesh)
    for name in ("adamw", "adafactor"):
        opt = make_optimizer(name)
        state_sds = jax.eval_shape(opt.init, sds)
        sspecs = opt.state_specs(pspecs, sds)
        _check_divisible(state_sds, sspecs, mesh)


def test_validate_rejects_overlong_spec():
    """A rule emitting more axes than the array has rank is a rule/shape
    mismatch — the regression was a silent truncation that sharded the wrong
    dims (or none)."""
    mesh = MESHES[0]
    with pytest.raises(ValueError, match="rank"):
        sh._validate(P(None, "model", None), (32, 64), mesh)
    # at-rank and under-rank specs still pass through (right-padded)
    assert sh._validate(P(None, "model"), (32, 64), mesh) == P(None, "model")
    assert sh._validate(P("data"), (32, 64), mesh) == P("data", None)


def test_param_specs_golden_packed_moe(key):
    """Golden specs over a packed MoE tree on a serving mesh (2 data × 4
    model) with head geometry: EP on data + TP inside each expert for the
    expert stacks, replicated router, whole-head-gated attention TP (MQA kv
    replicates: 1 head doesn't divide model=4)."""
    from repro.configs.registry import get_smoke_config

    mesh = AbstractMesh((2, 4), ("data", "model"))
    cfg = get_smoke_config("phi3.5-moe-42b-a6.6b").with_(n_layers=2)
    assert cfg.n_heads % 4 == 0 and cfg.n_kv_heads == 1
    sds = jax.eval_shape(functools.partial(init_params, cfg), key)
    packed = jax.eval_shape(
        functools.partial(quantize_for_serving, cfg=cfg), sds)
    specs = sh.param_specs(packed, mesh,
                           heads={"wq": cfg.n_heads, "wk": cfg.n_kv_heads})
    blocks = specs["blocks"]
    # expert stacks [L, E, dout, din/5]: EP on data, wi/wg shard the out
    # (dout) dim, wo the contraction it packs (din → model)
    assert blocks["moe"]["wi"]["packed"] == P(None, "data", "model", None)
    assert blocks["moe"]["wg"]["packed"] == P(None, "data", "model", None)
    assert blocks["moe"]["wo"]["packed"] == P(None, "data", None, "model")
    # router weight [L, d_model, E] is NOT an expert stack: replicated
    # (the regression sharded its d_model dim via the expert rule)
    router = jax.tree.leaves(blocks["moe"]["router"],
                             is_leaf=lambda s: isinstance(s, P))
    assert all(all(a is None for a in s) for s in router), \
        blocks["moe"]["router"]
    # attention: wq shards whole heads (4 % 4 == 0); MQA k/v replicate
    assert blocks["attn"]["wq"]["packed"] == P(None, "model", None)
    assert all(a is None for a in blocks["attn"]["wk"]["packed"])
    assert all(a is None for a in blocks["attn"]["wv"]["packed"])
    # packed wo [L, dout, din/5] is column-parallel (dout): sharding the
    # packed byte dim breaks the base-3 unpack's logical-K slice
    assert blocks["attn"]["wo"]["packed"] == P(None, "model", None)


def test_cache_specs_kv_head_gated():
    """Serving KV cache with ``kv_heads``: shard the head dim (whole heads),
    falling back to replication when the head count doesn't divide model —
    never the intra-head hd dim."""
    mesh = AbstractMesh((2, 4), ("data", "model"))
    kv = {"k": jax.ShapeDtypeStruct((2, 4, 64, 8, 32), jnp.bfloat16),
          "v": jax.ShapeDtypeStruct((2, 4, 64, 8, 32), jnp.bfloat16),
          "pos": jax.ShapeDtypeStruct((4,), jnp.int32)}
    ba = ("data",)
    specs = sh.cache_specs(kv, mesh, kv_heads=8)
    assert specs["k"] == P(None, ba, None, "model", None)
    assert specs["v"] == P(None, ba, None, "model", None)
    # MQA: 1 head can't split 4 ways — replicated, NOT silently hd-sharded
    mqa = {"k": jax.ShapeDtypeStruct((2, 4, 64, 1, 32), jnp.bfloat16)}
    assert sh.cache_specs(mqa, mesh, kv_heads=1)["k"] == \
        P(None, ba, None, None, None)
    # legacy (no kv_heads): hd-dim sharding as before
    assert sh.cache_specs(kv, mesh)["k"] == P(None, ba, None, None, "model")


def test_wz_partial_replication_gate():
    """wz (mamba2's elementwise gate projection) is TP'd only on a
    pure-model mesh; with a real batch axis alongside model it replicates
    (sharding._NO_TP_ROLES — CPU SPMD partial-replication miscompile)."""
    mixed = AbstractMesh((2, 4), ("data", "model"))
    pure = AbstractMesh((1, 8), ("data", "model"))
    path = ("blocks", "ssm", "wz", "w")
    assert sh._param_spec(path, 2, mixed) == P()
    assert sh._param_spec(path, 2, pure) == P(None, "model")


def test_batch_size_one_replicated():
    """long_500k (global_batch=1) must fall back to replication, not crash."""
    mesh = MESHES[0]
    specs = sh.batch_specs({"tokens": jax.ShapeDtypeStruct((1,), jnp.int32)}, mesh)
    assert specs["tokens"] == P(None)


def test_small_scale_jit_with_shardings(key):
    """End-to-end jit on a real 1-device mesh using the same sharding code
    path as the 512-chip dry-run."""
    from repro.configs.registry import get_smoke_config
    from repro.models.model import train_loss

    cfg = get_smoke_config("qwen3-0.6b")
    mesh = make_mesh_for(1)
    params = init_params(cfg, key)
    psh = sh.to_shardings(sh.param_specs(params, mesh), mesh)
    batch = {"tokens": jnp.ones((2, 16), jnp.int32),
             "labels": jnp.ones((2, 16), jnp.int32),
             "loss_mask": jnp.ones((2, 16), jnp.float32)}
    bsh = sh.to_shardings(sh.batch_specs(batch, mesh), mesh)
    fn = jax.jit(lambda p, b: train_loss(p, cfg, b)[0],
                 in_shardings=(psh, bsh))
    with mesh:
        loss = fn(jax.device_put(params, psh), jax.device_put(batch, bsh))
    assert jnp.isfinite(loss)
