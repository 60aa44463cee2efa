"""Configuration files against the program's config and BENCHMARK.json."""

import glob
import json
import os

import pytest

import config_map

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
# every configuration file, whether or not a cell uses it yet
CONFIGS = [next((c for c in BENCH["configs"] if c["file"] == rel),
                {"name": os.path.basename(rel)[:-5], "file": rel})
           for rel in sorted(os.path.relpath(p, ROOT) for p in glob.glob(
               os.path.join(ROOT, "bench", "configs", "*.json")))]


def _spec(entry):
    return config_map.load(os.path.join(ROOT, entry["file"]))


@pytest.mark.parametrize("entry", CONFIGS, ids=lambda e: e["name"])
def test_mapped_widths_equal_the_file(entry):
    spec = _spec(entry)
    cfg = config_map.to_program(spec).cfg
    for key, (path, width) in config_map.key_map(spec).items():
        if key not in spec:
            continue
        obj = cfg
        for part in path.split("."):
            obj = getattr(obj, part, None)
        if obj is not None:
            assert obj == spec[key], (key, path, obj)
    assert cfg.num_layers == spec["num_hidden_layers"]
    assert cfg.moe.num_experts == spec["n_routed_experts"]
    assert cfg.ess.host_cache_dtype == spec["serve"]["ess"]["host_cache_dtype"]


@pytest.mark.parametrize("entry", CONFIGS, ids=lambda e: e["name"])
def test_reduced_names_exactly_the_changed_keys(entry):
    spec = _spec(entry)
    published = spec["published"]
    for key, value in published.items():
        assert spec[key] != value, key
    if "reduced" in entry:
        assert sorted(entry["reduced"]) == sorted(published)
        assert entry["source"] == spec["source"]


@pytest.mark.parametrize("entry", CONFIGS, ids=lambda e: e["name"])
def test_not_taken_keys_are_reported(entry):
    got = config_map.to_program(_spec(entry)).not_taken
    for key in ("n_group", "topk_group", "topk_method", "rope_scaling",
                "scoring_func", "max_position_embeddings"):
        assert key in got
    for key in ("hidden_size", "index_topk", "n_routed_experts"):
        assert key not in got


def test_a_changed_width_fails():
    spec = _spec(CONFIGS[0])
    spec["kv_lora_rank"] = 256
    with pytest.raises(ValueError, match="kv_lora_rank"):
        config_map.to_program(spec)


def test_a_field_the_program_gains_is_taken():
    import dataclasses
    from repro.configs.base import ArchConfig

    @dataclasses.dataclass(frozen=True)
    class Later(ArchConfig):
        rope_scaling: object = None

    cfg = Later(name="x", family="moe", num_layers=1, d_model=1,
                num_heads=1, num_kv_heads=1, d_ff=1, vocab_size=1)
    got = config_map._set(cfg, "rope_scaling", {"type": "yarn"}, False,
                          "rope_scaling")
    assert got.rope_scaling == {"type": "yarn"}
    assert config_map._set(cfg, "moe.n_group", 8, False, "n_group") is None
