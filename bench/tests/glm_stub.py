"""A stand-in for GLM-5.2's architecture module (``model_type``
``glm_moe_dsa``) over the catalog entry's own configuration, copied
verbatim into ``data/glm_moe_dsa_catalog.json``: its key map names a leaf
of a nested group, a list and a width; it declares the keys the program
would not take and a stack of its own; and a program config of the
program's kind has the fields those keys land in."""

from __future__ import annotations

import dataclasses
import json
import os

from repro.configs import base

SOURCE = "https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json"
CATALOG = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "glm_moe_dsa_catalog.json")
# the catalog file as BENCHMARK.json would name it: nothing reduced
ENTRY = {"name": "glm52-catalog", "source": SOURCE,
         "file": "bench/tests/data/glm_moe_dsa_catalog.json", "reduced": []}

MODULE = '''
KEY_MAP = {
    "rope_parameters.rope_theta": ("rope_theta", False),
    "indexer_types": ("indexer_types", False),
    "rope_interleave": ("rope_interleaved", False),
    "kv_lora_rank": ("mla.kv_lora_rank", True),
}
NOT_TAKEN = (
    "ep_size", "first_k_dense_replace", "head_dim", "index_head_dim",
    "index_n_heads", "index_share_for_mtp_iteration",
    "index_skip_topk_offset", "index_topk", "index_topk_freq",
    "index_topk_pattern", "indexer_rope_interleave", "intermediate_size",
    "max_position_embeddings", "mlp_layer_types", "moe_intermediate_size",
    "moe_layer_freq", "n_group", "n_routed_experts", "n_shared_experts",
    "norm_topk_prob", "num_experts_per_tok", "num_nextn_predict_layers",
    "q_lora_rank", "qk_head_dim", "qk_nope_head_dim", "qk_rope_head_dim",
    "rope_parameters.rope_type", "routed_scaling_factor", "scoring_func",
    "topk_group", "topk_method", "v_head_dim")
# indexer weights held for the full layers only, in a stack of their own
STACKS = ("index_layers",)
'''


@dataclasses.dataclass(frozen=True)
class StubConfig(base.ArchConfig):
    indexer_types: tuple[str, ...] = ()


def program_config() -> StubConfig:
    return StubConfig(name="stub-glm", family="moe", num_layers=1,
                      d_model=6144, num_heads=64, num_kv_heads=64, d_ff=1,
                      vocab_size=1, attn_kind="mla", mla=base.MLAConfig())


def install(tmp_path, monkeypatch, config_map) -> dict:
    """Put the stub module alone in ``config_map.ARCHS`` and its program
    config in the program's registry; return the configuration file: the
    catalog's config with the keys the harness adds to every file."""
    (tmp_path / "glm_moe_dsa.py").write_text(MODULE)
    monkeypatch.setattr(config_map, "ARCHS", str(tmp_path))
    monkeypatch.setitem(base._REGISTRY, "stub-glm", program_config)
    with open(CATALOG) as f:
        spec = json.load(f)
    return dict(spec, source=SOURCE, published={},
                serve={"arch": "stub-glm",
                       "ess": {"host_cache_dtype": "int8"}})
