"""Configuration files against the program's config and BENCHMARK.json,
each held to its own architecture module: every file of ``bench/configs``,
and the GLM-5.2 catalog entry through a stand-in ``glm_moe_dsa`` module
(``glm_stub.py``)."""

import glob
import json
import os

import pytest

import config_map
import glm_stub

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
# every configuration file, whether or not a cell uses it yet
CONFIGS = [next((c for c in BENCH["configs"] if c["file"] == rel),
                {"name": os.path.basename(rel)[:-5], "file": rel})
           for rel in sorted(os.path.relpath(p, ROOT) for p in glob.glob(
               os.path.join(ROOT, "bench", "configs", "*.json")))]


CASES = CONFIGS + [glm_stub.ENTRY]
# keys every catalog entry shares, taken wherever the file's table maps them
SHARED = ("hidden_size", "index_topk", "n_routed_experts")


def _spec(entry):
    return config_map.load(os.path.join(ROOT, entry["file"]))


@pytest.fixture
def spec_of(tmp_path, monkeypatch):
    """The configuration file of a case; the stand-in's case installs its
    module first."""
    def get(entry):
        if entry["name"] == glm_stub.ENTRY["name"]:
            return glm_stub.install(tmp_path, monkeypatch, config_map)
        return _spec(entry)
    return get


def _published(spec, key):
    """The file's value at a (dotted) published key, or KeyError."""
    for part in key.split("."):
        spec = spec[part]
    return spec


@pytest.mark.parametrize("entry", CASES, ids=lambda e: e["name"])
def test_mapped_widths_equal_the_file(entry, spec_of):
    spec = spec_of(entry)
    cfg = config_map.to_program(spec).cfg
    table = config_map.key_map(spec)
    for key, (path, width) in table.items():
        try:
            value = _published(spec, key)
        except KeyError:
            continue
        obj = cfg
        for part in path.split("."):
            obj = getattr(obj, part, None)
        if obj is not None:
            assert obj == config_map.frozen(value), (key, path, obj)
    assert cfg.num_layers == spec["num_hidden_layers"]
    if "n_routed_experts" in table:
        assert cfg.moe.num_experts == spec["n_routed_experts"]
    assert cfg.ess.host_cache_dtype == spec["serve"]["ess"]["host_cache_dtype"]


@pytest.mark.parametrize("entry", CASES, ids=lambda e: e["name"])
def test_reduced_names_exactly_the_changed_keys(entry, spec_of):
    spec = spec_of(entry)
    published = spec["published"]
    for key, value in published.items():
        assert spec[key] != value, key
    if "reduced" in entry:
        assert sorted(entry["reduced"]) == sorted(published)
        assert entry["source"] == spec["source"]


@pytest.mark.parametrize("entry", CASES, ids=lambda e: e["name"])
def test_not_taken_keys_are_reported(entry, spec_of):
    spec = spec_of(entry)
    got = config_map.to_program(spec).not_taken
    for key in config_map.arch_module(spec).NOT_TAKEN:
        assert key in got
    table = config_map.key_map(spec)
    for key in SHARED:
        if key in table:
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
