"""Work counts and the peaks table, against values worked out by hand: the
shared rules of ``bench/work.py`` and bitnet's counts in
``bench/arch/bitnet.py``."""

import json
from pathlib import Path

import pytest

from bench import work
from bench.arch import bitnet

ROOT = Path(__file__).resolve().parents[2]
MODEL = json.loads((ROOT / "bench" / "configs" / "bitnet2b-bf16.json")
                   .read_text())

# (role, K, N) of bitnet-b1.58-2b: d 2560, 20 heads and 5 KV heads of 128,
# FFN 6912
SHAPES = [("q", 2560, 2560), ("k", 2560, 640), ("v", 2560, 640),
          ("o", 2560, 2560), ("gate", 2560, 6912), ("up", 2560, 6912),
          ("down", 6912, 2560)]


def test_projection_shapes():
    assert bitnet.projections(MODEL) == SHAPES


@pytest.mark.parametrize("m,k,n,act,flops,nbytes", [
    # q at a 32-row decode step, bf16 activations:
    # 2*32*2560*2560; 2560*2560/5 packed + 32*2560*2 in + 32*2560*2 out
    (32, 2560, 2560, 2, 419_430_400, 1_310_720 + 163_840 + 163_840),
    # down at one 32-token prefill chunk, int8 activations:
    # 2*32*6912*2560; 6912*2560/5 + 32*6912*1 + 32*2560*2
    (32, 6912, 2560, 1, 1_132_462_080, 3_538_944 + 221_184 + 163_840),
    # k at a single decode row: 2*2560*640; 2560*640/5 + 2560*2 + 640*2
    (1, 2560, 640, 2, 3_276_800, 327_680 + 5_120 + 1_280),
])
def test_projection_work(m, k, n, act, flops, nbytes):
    f, b = work.projection_work(m, k, n, act)
    assert f == flops
    assert b == pytest.approx(nbytes, rel=1e-12)


def test_model_flops_per_token():
    # ternary parameters per layer: 2*2560^2 + 2*2560*640 + 3*2560*6912
    per_layer = 13_107_200 + 3_276_800 + 53_084_160
    assert bitnet.ternary_params(MODEL) == 30 * per_layer == 2_084_044_800
    # LM head: 2 * 2560 * 128256
    assert work.head_flops(MODEL) == 656_670_720
    # attention at 1000 positions: 4 * 30 layers * 20 heads * 128 * 1000
    assert bitnet.attention_flops(MODEL, 1000) == 307_200_000
    assert bitnet.token_flops(MODEL, 1000, head=True) == \
        2 * 2_084_044_800 + 307_200_000 + 656_670_720
    assert bitnet.token_flops(MODEL, 1, head=False) == \
        2 * 2_084_044_800 + 4 * 30 * 20 * 128


def test_least_seconds_takes_the_larger_bound_per_projection():
    pk = work.peaks("TPU v5 lite")
    want = 0.0
    for _, k, n in SHAPES:
        f = 2 * 8 * k * n
        b = k * n / 5 + 8 * k * 2 + 8 * n * 2
        want += max(f / 197e12, b / 819e9)
    got = bitnet.least_seconds(8, MODEL, 2, pk["bf16_flops"],
                               pk["hbm_bytes_per_s"])
    assert got == pytest.approx(30 * want, rel=1e-12)


def test_peaks_of_a_v5e():
    pk = work.peaks("TPU v5 lite")
    assert (pk["bf16_flops"], pk["int8_ops"], pk["hbm_bytes_per_s"]) == \
        (197e12, 393e12, 819e9)


def test_an_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")
