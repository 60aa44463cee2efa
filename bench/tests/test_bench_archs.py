"""An architecture enters the benchmark as one module of its own,
``bench/archs/<model_type>.py``, found by the configuration file's
``model_type``: its published keys, its reference and its operation
counts, read through ``config_map``, ``reference`` and ``flops`` with no
edit to them."""

import dataclasses
import hashlib
import json
import math
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import config_map
import flops
import glm_stub
import reference
import run
import tiny
import weights

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

STUB = textwrap.dedent('''
    """A stand-in architecture: one key of its own, a reference whose
    final hidden is all ones, and fixed counts."""
    import jax.numpy as jnp

    from reference import Base

    KEY_MAP = {"stub_width": ("d_model", True),
               "stub_layers": ("num_layers", False)}


    class Reference(Base):
        def hidden(self, tokens, first):
            return jnp.ones((tokens.shape[0] - first,
                             self.c["hidden_size"]), jnp.float32)


    class Model:
        def __init__(self, spec):
            self.weight_macs = 7 * spec["hidden_size"]
    ''')


def test_a_new_model_type_needs_only_its_own_module(tmp_path, monkeypatch):
    (tmp_path / "stub_arch.py").write_text(STUB)
    monkeypatch.setattr(config_map, "ARCHS", str(tmp_path))
    spec = dict(tiny.CONFIG, model_type="stub_arch", stub_width=64,
                stub_layers=2)
    mapped = config_map.to_program(spec)
    assert mapped.cfg.num_layers == 2
    assert "stub_layers" not in mapped.not_taken
    # the DeepSeek module's keys are its own, not shared
    assert "kv_lora_rank" in mapped.not_taken
    assert "hidden_size" not in mapped.not_taken
    with pytest.raises(ValueError, match="stub_width"):
        config_map.to_program(dict(spec, stub_width=65))
    assert flops.Model(spec).weight_macs == 7 * 64
    unembed = np.random.default_rng(0).normal(size=(256, 64))
    params = {"final_norm": jnp.zeros(64), "unembed": jnp.asarray(unembed)}
    ref = reference.Reference(spec, params, 64, block=16)
    assert type(ref).__module__ == "bench_arch_stub_arch"
    # all-ones hidden: the same logits at every position, whose best
    # token is served
    best = int(np.argmax(unembed.sum(-1)))
    got = reference.served_gaps(ref, np.arange(5, dtype=np.int32),
                                [best] * 3)
    assert got == {"served_gap": 0.0, "n": 3}


@pytest.mark.parametrize("entry", [
    lambda s: config_map.to_program(s),
    lambda s: reference.Reference(s, {}, 64),
    lambda s: flops.Model(s)], ids=["config_map", "reference", "flops"])
def test_an_unknown_model_type_names_the_missing_module(entry):
    spec = dict(tiny.CONFIG, model_type="no_such_arch")
    with pytest.raises(FileNotFoundError,
                       match=r"bench/archs/no_such_arch\.py"):
        entry(spec)


# the parent's counts before they moved into bench/archs/deepseek_v32.py:
# weight_macs, weight_bytes, token_flops(1), token_flops(40000),
# decode_round_bytes([32768, 33000, 40000, 1], row bytes), row bytes
COUNTS = {
    "dsv32-exp-int8": (3500990464, 7001980928, 7003160576.0, 11905122304.0,
                       7124495624, 578),
    "dsv32-exp-bf16": (3500990464, 7001980928, 7003160576.0, 11905122304.0,
                       7138604544, 1152),
}


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_deepseek_counts_are_the_parents(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        spec = json.load(f)
    rb = run.row_bytes(spec, spec["serve"]["ess"]["host_cache_dtype"])
    m = flops.Model(spec)
    assert (m.weight_macs, m.weight_bytes, m.token_flops(1),
            m.token_flops(40000),
            m.decode_round_bytes([32768, 33000, 40000, 1], rb), rb) == \
        COUNTS[name]


def test_glm_catalog_file_maps_through_its_own_module(tmp_path, monkeypatch):
    spec = glm_stub.install(tmp_path, monkeypatch, config_map)
    mapped = config_map.to_program(spec)
    cfg = mapped.cfg
    assert cfg.rope_theta == 8000000.0 and type(cfg.rope_theta) is float
    assert cfg.indexer_types == tuple(spec["indexer_types"])
    assert len(cfg.indexer_types) == 78
    assert cfg.rope_interleaved is True
    hash(cfg)
    hash(config_map.frozen(spec))
    assert mapped.not_taken == \
        sorted(config_map.arch_module(spec).NOT_TAKEN)
    with pytest.raises(ValueError, match="kv_lora_rank"):
        config_map.to_program(dict(spec, kv_lora_rank=256))


def test_a_width_inside_a_group_is_checked(tmp_path, monkeypatch):
    spec = glm_stub.install(tmp_path, monkeypatch, config_map)
    monkeypatch.setitem(config_map.arch_module(spec).KEY_MAP,
                        "rope_parameters.rope_dim",
                        ("mla.qk_rope_head_dim", True))
    group = dict(spec["rope_parameters"], rope_dim=64)
    got = config_map.to_program(dict(spec, rope_parameters=group))
    assert "rope_parameters.rope_dim" not in got.not_taken
    assert "rope_parameters.rope_type" in got.not_taken
    with pytest.raises(ValueError, match="rope_parameters.rope_dim"):
        config_map.to_program(dict(spec, rope_parameters=dict(
            group, rope_dim=32)))


def test_an_architecture_stack_draws_without_its_depth(tmp_path,
                                                        monkeypatch):
    arch = config_map.arch_module(
        glm_stub.install(tmp_path, monkeypatch, config_map))
    path = (jax.tree_util.DictKey("index_layers"),
            jax.tree_util.DictKey("w_iq"))
    shape = (4, 6144, 32, 128)
    assert weights.leaf_std(path, shape, arch) == 1.0 / math.sqrt(6144)
    assert weights.leaf_std(path, shape, None) == 1.0 / math.sqrt(4)
    abstract = {"index_layers": {"w_iq": jax.ShapeDtypeStruct(
        (2, 64, 4, 16), jnp.float32)}}
    drawn = weights.make(abstract, 11, arch)["index_layers"]["w_iq"]
    assert abs(float(jnp.std(drawn)) * 8 - 1) < 0.05


DSV32 = ("dsv32-exp-int8", "dsv32-exp-bf16")


def _dsv32(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _flat_to_program(spec):
    """The parent's mapping, kept as the reference: top-level keys only,
    each value as the file holds it."""
    from repro.configs import get_config
    cfg = get_config(spec["serve"]["arch"])
    table = config_map.key_map(spec)
    not_taken = []
    for key, value in spec.items():
        if key in config_map.META:
            continue
        new = None
        if key in table:
            path, width = table[key]
            new = config_map._set(cfg, path, value, width, key)
        if new is None:
            not_taken.append(key)
        else:
            cfg = new
    cfg = dataclasses.replace(
        cfg, ess=dataclasses.replace(cfg.ess, **spec["serve"]["ess"]))
    return cfg, sorted(not_taken)


@pytest.mark.parametrize("name", DSV32)
def test_deepseek_mapping_is_the_parents(name):
    spec = _dsv32(name)
    mapped = config_map.to_program(spec)
    cfg, not_taken = _flat_to_program(spec)
    assert mapped.cfg == cfg
    assert mapped.not_taken == not_taken == [
        "ep_size", "max_position_embeddings", "moe_layer_freq", "n_group",
        "rope_scaling", "scoring_func", "topk_group", "topk_method"]
    assert mapped.mtp_depth == 0


@pytest.mark.parametrize("name", DSV32)
def test_deepseek_leaf_stds_are_the_parents(name):
    """Every leaf of the program's tree at published widths, in draw
    order, with the parent's fan-in (None: a zero leaf)."""
    from repro.models import transformer as T
    from repro.models.params import abstract_params
    spec = _dsv32(name)
    arch = config_map.arch_module(spec)
    leaves = jax.tree_util.tree_flatten_with_path(abstract_params(
        T.model_def(config_map.to_program(spec).cfg)))[0]
    with open(os.path.join(ROOT, "bench", "tests", "data",
                           "dsv32_leaf_fans.json")) as f:
        want = json.load(f)
    got = [["/".join(k.key for k in path), list(a.shape)]
           for path, a in leaves]
    assert got == [row[:2] for row in want]
    for (path, a), (_, _, fan) in zip(leaves, want):
        std = weights.leaf_std(path, tuple(a.shape), arch)
        assert std == (None if fan is None else 1.0 / math.sqrt(fan))


def test_deepseek_draw_is_the_parents():
    """The tiny DeepSeek tree's draw, byte for byte (float32 view)."""
    from repro.models import transformer as T
    from repro.models.params import abstract_params
    spec = tiny.CONFIG
    params = weights.make(
        abstract_params(T.model_def(config_map.to_program(spec).cfg)),
        2 ** 33 + 7, config_map.arch_module(spec))
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf.astype(jnp.float32)).tobytes())
    assert h.hexdigest() == \
        "c6c0759eaf05e5589d01e719e9f3e76bac32aba922d2b9f13f9893424d9fcd36"
