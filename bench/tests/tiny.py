"""A tiny DeepSeek-V3.2-shaped cell for CPU tests of the harness: the
program's ``deepseek-v32-exp-ess-smoke`` widths and a closed traffic mix,
written into a temporary tree shaped like ``bench/``."""

from __future__ import annotations

import json
import os

CONFIG = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 64, "index_head_dim": 16, "index_n_heads": 2,
    "index_topk": 1024, "intermediate_size": 128, "kv_lora_rank": 32,
    "max_position_embeddings": 4096, "model_type": "deepseek_v32",
    "moe_intermediate_size": 64, "n_group": 2, "n_routed_experts": 2,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 4,
    "num_key_value_heads": 4, "num_nextn_predict_layers": 1,
    "q_lora_rank": 48, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "rms_norm_eps": 1e-06, "rope_theta": 10000, "routed_scaling_factor": 1.0,
    "tie_word_embeddings": False, "topk_group": 1, "v_head_dim": 16,
    "vocab_size": 256,
    "serve": {"arch": "deepseek-v32-exp-ess-smoke", "mtp_depth": 0,
              "ess": {"sparse_memory_ratio": 0.5, "max_miss_ratio": 1.0,
                      "warmup_windows": 4, "overlap": "da",
                      "pool_min_entries": 8, "host_page_rows": 16,
                      "host_cache_dtype": "int8"}},
}
CLOSED = {"kind": "closed", "requests": 3,
          "prompt": {"dist": "fixed", "tokens": 40},
          "output": {"dist": "fixed", "tokens": 984},
          "engine": {"num_slots": 3, "max_seq": 1024, "prefill_chunk": 16},
          "warm_rounds": 2, "check_requests": 2, "trace_seconds": 0.5}


def make_tree(tmp: str, limit: float = 0.05) -> dict:
    """Write the tiny config, its mix and its limit under ``tmp``;
    return the BENCHMARK.json-shaped dict that names them."""
    for d in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(tmp, d), exist_ok=True)

    def put(rel, obj):
        with open(os.path.join(tmp, rel), "w") as f:
            json.dump(obj, f)

    put("configs/tiny.json", CONFIG)
    put("traffic/tiny-closed.json", CLOSED)
    put("limits/tiny.closed.json", {"served_gap": {"limit": limit}})
    return {
        "configs": [{"name": "tiny", "file": "configs/tiny.json"}],
        "workloads": [
            {"name": "tiny.closed", "config": "tiny",
             "traffic": "tiny-closed", "chips": 1}],
        "end_to_end": [
            {"name": "decode_tok_s", "unit": "tokens/s",
             "workloads": ["tiny.closed"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [],
    }
