"""The serving path's ternary kernels compile for a TPU v5e.

Nothing here runs on a chip: the TPU compiler compiles for a v5e that is
described, not attached.  The kernels are compiled with ``interpret=False``
at bitnet-b1.58-2b's published widths — decode (M = batch) and one prefill
bucket (M = prefill chunk), bf16 and int8 activations — for the kernel
``select_kernel(..., backend="tpu")`` picks and for every kernel ``auto``
may pick there.  The selection tests alongside check the explicit TPU
exclusions those compiles rely on.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and the test workers each import
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels import dispatch as dp
from repro.models.decode import layer_matmul_problems

CFG = get_config("bitnet-b1.58-2b")
BATCH = 4            # decode M
PREFILL_CHUNK = 32   # one prefill bucket: DecodeEngine's default chunk
DTYPES = ["bfloat16", "int8"]
#: bitnet's dense projection problems (M, K, N): decode and prefill
PROBLEMS = sorted({(m, k, n)
                   for rows, seq in ((BATCH, 1), (1, PREFILL_CHUNK))
                   for _, m, k, n in layer_matmul_problems(CFG, rows, seq)})
#: a grouped (MoE) decode problem at phi3.5-moe's widths: E, C, K, N
GROUPED = (16, 1, 4096, 6400)
EMPTY_CACHE = dp.AutotuneCache(path="/nonexistent/autotune.json")
NO_TPU = sorted(n for n, s in dp.REGISTRY.items() if not s.tpu)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(spec, one_chip, m, k, n, act, e=None):
    """Compile ``spec.run`` for the described chip; returns the text."""
    lead = () if e is None else (e,)
    x = jax.ShapeDtypeStruct((*lead, m, k), jnp.dtype(act), sharding=one_chip)
    nb = -(-k // 5)
    nb += (-nb) % 128   # the serving artifact's 128-byte-aligned rows
    p = jax.ShapeDtypeStruct((*lead, n, nb), jnp.uint8, sharding=one_chip)
    cls = dp.TernaryWeight if e is None else dp.GroupedTernaryWeight

    def f(xx, pk):
        return spec.run(xx, cls.from_packed(pk, 1.0, k), 3, False)

    return jax.jit(f).lower(x, p).compile().as_text()


@pytest.mark.parametrize("m,k,n", PROBLEMS)
@pytest.mark.parametrize("act", DTYPES)
def test_selected_kernel_compiles(one_chip, act, m, k, n):
    """What ``auto`` serves on TPU compiles, and decode streams the packed
    weights through a Mosaic kernel rather than the XLA ``ref``."""
    spec = dp.select_kernel(m, k, n, act, policy="auto", backend="tpu",
                            cache=EMPTY_CACHE)
    assert spec.name == {"bfloat16": "dequant_packed", "int8": "w2a8"}[act]
    text = _compile(spec, one_chip, m, k, n, act)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("act", DTYPES)
def test_every_tpu_eligible_kernel_compiles(one_chip, act):
    names = []
    for m, k, n in ((BATCH, CFG.d_model, CFG.d_ff),
                    (PREFILL_CHUNK, CFG.d_ff, CFG.d_model)):
        for spec in dp.eligible_kernels(m, k, n, act, backend="tpu"):
            _compile(spec, one_chip, m, k, n, act)
            names.append(spec.name)
    assert {"ref", "dequant_packed"} <= set(names)
    assert not set(names) & set(NO_TPU)


@pytest.mark.parametrize("act", DTYPES)
def test_every_tpu_eligible_grouped_kernel_compiles(one_chip, act):
    e, c, k, n = GROUPED
    specs = dp.eligible_kernels(c, k, n, act, e, backend="tpu")
    assert "grouped_dequant" in {s.name for s in specs}
    for spec in specs:
        _compile(spec, one_chip, c, k, n, act, e=e)


# ---------------------------------------------------------------------------
# the explicit exclusions (no topology needed)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", NO_TPU)
def test_excluded_kernel_never_selected_on_tpu(name):
    """Not by the prior, and not even when the autotune cache says it is
    fastest."""
    cache = dp.AutotuneCache(path="/nonexistent/autotune.json")
    for m, k, n in PROBLEMS:
        for act in DTYPES:
            cache.record(m, k, n, act, "tpu", name, 0.1)
            for policy in ("auto", "prior"):
                spec = dp.select_kernel(m, k, n, act, policy=policy,
                                        backend="tpu", cache=cache)
                assert spec.tpu and spec.name != name


@pytest.mark.parametrize("name", NO_TPU)
def test_fixed_pin_of_excluded_kernel_raises_on_tpu(name):
    with pytest.raises(ValueError, match=name):
        dp.select_kernel(BATCH, CFG.d_model, CFG.d_ff, "bfloat16",
                         policy=f"fixed:{name}", backend="tpu")
    # the same pin is still honoured where the kernel runs (interpreted)
    assert dp.select_kernel(BATCH, CFG.d_model, CFG.d_ff, "bfloat16",
                            policy=f"fixed:{name}", backend="cpu").name == name


def test_partitioned_mesh_excludes_pallas_on_tpu():
    """GSPMD cannot partition a Mosaic call: under a partitioned shard scope
    only XLA kernels are eligible on TPU, and a Pallas pin raises."""
    with dp.shard_scope(dp.ShardInfo(model=4)):
        for m, k, n in PROBLEMS:
            for act in DTYPES:
                spec = dp.select_kernel(m, k, n, act, backend="tpu",
                                        cache=EMPTY_CACHE, role="wq")
                assert not spec.pallas
        with pytest.raises(ValueError, match="dequant_packed"):
            dp.select_kernel(BATCH, CFG.d_model, CFG.d_ff, "bfloat16",
                             policy="fixed:dequant_packed", backend="tpu")
    # a one-device scope partitions nothing
    with dp.shard_scope(dp.ShardInfo()):
        assert dp.select_kernel(BATCH, CFG.d_model, CFG.d_ff, "bfloat16",
                                backend="tpu", cache=EMPTY_CACHE).pallas
