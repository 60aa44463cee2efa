"""An architecture enters the benchmark as one module of its own,
``bench/archs/<model_type>.py``, found by the configuration file's
``model_type``: its published keys, its reference and its operation
counts, read through ``config_map``, ``reference`` and ``flops`` with no
edit to them."""

import json
import os
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

import config_map
import flops
import reference
import run
import tiny

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
