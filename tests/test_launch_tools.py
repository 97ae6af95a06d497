"""Launch-layer tooling: report rendering, roofline math,
collective parsing edge cases."""

import pytest

from repro.launch import hlo_cost, mesh as mesh_mod, roofline as rl
from repro.launch.report import dryrun_table, roofline_table, summary


def _fake_rec(arch="a", shape="train_4k", mesh="16x16", **kw):
    roof = rl.Roofline(chips=256, flops_per_device=1e12, bytes_per_device=1e11,
                       collective_bytes_per_device=1e10)
    r = {"arch": arch, "shape": shape, "mesh": mesh, "ok": True,
         "roofline": roof.as_dict(), "model_flops_ratio": 0.7,
         "param_bytes_per_device": 1e9, "compile_s": 10,
         "memory_analysis": {"temp_size_in_bytes": int(2e9)}}
    r.update(kw)
    return r


def test_roofline_terms_and_bottleneck():
    r = rl.Roofline(chips=256, flops_per_device=197e12,
                    bytes_per_device=819e9, collective_bytes_per_device=0.0)
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(1.0)
    assert r.bottleneck in ("compute", "memory")
    r2 = rl.Roofline(chips=256, flops_per_device=0, bytes_per_device=0,
                     collective_bytes_per_device=50e9)
    assert r2.bottleneck == "collective" and r2.step_time_s == pytest.approx(1.0)


def test_model_flops_kinds():
    from repro.configs.registry import get_config
    from repro.configs.shapes import SHAPES

    cfg = get_config("qwen3-0.6b")
    tr = rl.model_flops(cfg, SHAPES["train_4k"], "train")
    pf = rl.model_flops(cfg, SHAPES["prefill_32k"], "prefill")
    de = rl.model_flops(cfg, SHAPES["decode_32k"], "decode")
    assert tr == 6 * cfg.active_param_count() * 256 * 4096
    assert pf == 2 * cfg.active_param_count() * 32 * 32768
    assert de == 2 * cfg.active_param_count() * 128


def test_report_tables_render():
    recs = [_fake_rec(), _fake_rec(mesh="2x16x16"),
            _fake_rec(arch="b", shape="decode_32k",
                      cache_bytes_per_device=3e9)]
    t1 = roofline_table(recs)
    assert "| a | train_4k |" in t1
    t2 = dryrun_table(recs)
    assert "2x16x16" in t2
    assert "cells compiled" in summary(recs)


def test_collective_parser_shapes():
    text = """HloModule m
ENTRY %main (p: f32[16]) -> f32[16] {
  %p = f32[16]{0} parameter(0)
  %ag = f32[256]{0} all-gather(%p), replica_groups=[16,16]<=[256], dimensions={0}
  %rs = f32[16]{0} reduce-scatter(%ag), replica_groups=[16,16]<=[256], dimensions={0}, to_apply=%add
  ROOT %ar = f32[16]{0} all-reduce(%rs), replica_groups=[16,16]<=[256], to_apply=%add
}
"""
    r = hlo_cost.analyze(text)
    assert r["collectives"]["all-gather"] == 256 * 4
    assert r["collectives"]["reduce-scatter"] == 16 * 4 * 16  # scaled by group
    assert r["collectives"]["all-reduce"] == 16 * 4


def test_hardware_constants():
    assert mesh_mod.PEAK_FLOPS_BF16 == 197e12
    assert mesh_mod.HBM_BW == 819e9
    assert mesh_mod.ICI_BW == 50e9
    assert mesh_mod.CHIPS_MULTI_POD == 2 * 256
