"""From a configuration file of ``bench/configs`` to the program's config.

``KEY_MAP`` is the one table: each key of the published config names the
field of the program's config dataclasses it sets (``moe.top_k`` is field
``top_k`` of ``cfg.moe``).  A key whose field the program's dataclasses
lack today is reported as not taken; once a later change adds the field
under that name, the key is applied with no edit here.  A width must
equal the field it maps onto, or the run fails.  Keys absent from the
table are reported as not taken too.

``serve`` holds the engine settings that are not model keys: the
program's registered architecture (``arch``), the ESS options
(``serve.ess`` -> ``cfg.ess``) and the MTP depth served.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any

# published key -> (field path in the program's ArchConfig, is a width)
KEY_MAP: dict[str, tuple[str, bool]] = {
    "hidden_size": ("d_model", True),
    "num_attention_heads": ("num_heads", True),
    "num_key_value_heads": ("num_kv_heads", True),
    "intermediate_size": ("moe.dense_d_ff", True),
    "moe_intermediate_size": ("moe.d_expert", True),
    "q_lora_rank": ("mla.q_lora_rank", True),
    "kv_lora_rank": ("mla.kv_lora_rank", True),
    "qk_nope_head_dim": ("mla.qk_nope_head_dim", True),
    "qk_rope_head_dim": ("mla.qk_rope_head_dim", True),
    "v_head_dim": ("mla.v_head_dim", True),
    "index_n_heads": ("dsa.index_heads", True),
    "index_head_dim": ("dsa.index_dim", True),
    "num_experts_per_tok": ("moe.top_k", True),
    "vocab_size": ("vocab_size", False),
    "index_topk": ("dsa.index_topk", False),
    "num_hidden_layers": ("num_layers", False),
    "n_routed_experts": ("moe.num_experts", False),
    "n_shared_experts": ("moe.num_shared", False),
    "first_k_dense_replace": ("moe.first_dense_layers", False),
    "routed_scaling_factor": ("moe.routed_scale", False),
    "norm_topk_prob": ("moe.norm_topk", False),
    "rms_norm_eps": ("norm_eps", False),
    "rope_theta": ("rope_theta", False),
    "num_nextn_predict_layers": ("mtp_depth", False),
    "tie_word_embeddings": ("tie_embeddings", False),
    "attention_bias": ("qkv_bias", False),
    "hidden_act": ("act", False),
    "max_position_embeddings": ("max_position_embeddings", False),
    "n_group": ("moe.n_group", False),
    "topk_group": ("moe.topk_group", False),
    "topk_method": ("moe.topk_method", False),
    "scoring_func": ("moe.scoring_func", False),
    "moe_layer_freq": ("moe.layer_freq", False),
    "ep_size": ("moe.ep_size", False),
    "rope_scaling": ("rope_scaling", False),
}
# keys of the file that describe it rather than the model
META = ("source", "published", "deployment", "departures", "serve",
        "assumed", "model_type")


@dataclasses.dataclass
class Mapped:
    cfg: Any                      # the program's ArchConfig
    not_taken: list[str]          # published keys the program ignores
    mtp_depth: int                # MTP depth the engine serves


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _coerce(old, new):
    if isinstance(old, bool) or old is None:
        return new
    if isinstance(old, float):
        return float(new)
    if isinstance(old, int):
        return int(new)
    return new


def _set(cfg, path: str, value, width: bool, key: str):
    """Return ``cfg`` with field ``path`` set to ``value``, or None when
    the program's dataclass has no such field."""
    head, _, rest = path.partition(".")
    if not dataclasses.is_dataclass(cfg) or \
            head not in {f.name for f in dataclasses.fields(cfg)}:
        return None
    cur = getattr(cfg, head)
    if rest:
        sub = _set(cur, rest, value, width, key)
        return None if sub is None else dataclasses.replace(cfg, **{head: sub})
    if width:
        if cur != value:
            raise ValueError(f"width {key} = {value} in the file, "
                             f"{path} = {cur} in the program")
        return cfg
    return dataclasses.replace(cfg, **{head: _coerce(cur, value)})


def to_program(spec: dict) -> Mapped:
    """Build the program's ArchConfig from a configuration file."""
    from repro.configs import get_config
    serve = spec["serve"]
    cfg = get_config(serve["arch"])
    not_taken = []
    for key, value in spec.items():
        if key in META:
            continue
        if key not in KEY_MAP:
            not_taken.append(key)
            continue
        path, width = KEY_MAP[key]
        new = _set(cfg, path, value, width, key)
        if new is None:
            not_taken.append(key)
        else:
            cfg = new
    ess = serve.get("ess")
    if ess:
        cfg = dataclasses.replace(
            cfg, ess=dataclasses.replace(cfg.ess, **ess))
    return Mapped(cfg, sorted(not_taken), int(serve.get("mtp_depth", 0)))
