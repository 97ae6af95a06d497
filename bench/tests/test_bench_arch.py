"""Each configuration names its architecture module, and the harness finds
the reference, the keys the program is held to and the work counts through
it (``bench.arch.load``)."""

import json
from pathlib import Path

import pytest

from bench import arch, harness

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = sorted((ROOT / "bench" / "configs").glob("*.json"))


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_every_configuration_names_a_module_with_the_four_names(path):
    mod = arch.load(json.loads(path.read_text()), path.name)
    for name in ("Reference", "PROGRAM_KEYS", "least_seconds", "token_flops"):
        assert hasattr(mod, name), name


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_the_program_agrees_with_every_mapped_key(path):
    model = json.loads(path.read_text())
    cfg = harness.program_config(model)
    for key, attr in arch.load(model).PROGRAM_KEYS.items():
        assert getattr(cfg, attr) == model[key], key


@pytest.mark.parametrize("model,says", [
    ({"name": "x"}, "names no architecture"),
    ({"name": "x", "architecture": "mamba9"}, "'mamba9', which has no"),
    ({"name": "x", "architecture": "../bitnet"}, "names no architecture"),
])
def test_a_missing_or_unknown_architecture_names_the_file(model, says):
    with pytest.raises(ValueError, match=says) as e:
        arch.load(model, "bench/configs/x.json")
    assert "bench/configs/x.json" in str(e.value)
    assert "bitnet" in str(e.value)


def test_a_cell_whose_configuration_names_no_module_fails_to_load(tmp_path):
    model = json.loads((ROOT / "bench" / "configs" / "bitnet2b-int8.json")
                       .read_text())
    del model["architecture"]
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "configs" / "m.json").write_text(json.dumps(model))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [dict(bench["configs"][0], file="bench/configs/m.json")]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(ValueError, match="bench/configs/m.json"):
        harness.load_cell(bench["workloads"][0]["name"], tmp_path)


@pytest.mark.parametrize("key,value", [
    ("intermediate_size", 6913), ("num_key_value_heads", 4),
    ("rope_theta", 500000.0), ("tie_word_embeddings", True),
])
def test_program_config_refuses_a_mapped_key_the_program_disagrees_with(
        key, value):
    model = json.loads((ROOT / "bench" / "configs" / "bitnet2b-int8.json")
                       .read_text())
    model[key] = value
    attr = arch.load(model).PROGRAM_KEYS[key]
    with pytest.raises(ValueError, match=attr):
        harness.program_config(model)
