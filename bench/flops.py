"""Operations and bytes of the served model, from the configuration file.

Model FLOPs count each multiply-add as 2 and only the work the model
needs: every weight product of a token once (the ``num_experts_per_tok``
routed experts of those held here, the shared expert, the output head),
the indexer over the live context, and MLA over at most ``index_topk``
selected rows (absorbed form: 576-wide scores, 512-wide values).  Work
the program does beyond that (scoring padding, the MTP module it does
not serve) does not count.
"""

from __future__ import annotations

import json


class Model:
    """Per-token operation counts of one configuration file."""

    def __init__(self, spec: dict):
        c = spec
        d = c["hidden_size"]
        H = c["num_attention_heads"]
        r, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
        nope, v = c["qk_nope_head_dim"], c["v_head_dim"]
        ql = c["q_lora_rank"]
        Hi, Di = c["index_n_heads"], c["index_head_dim"]
        E = c["n_routed_experts"]
        k = min(c["num_experts_per_tok"], E)
        fe = c["moe_intermediate_size"]
        L, nd = c["num_hidden_layers"], c["first_k_dense_replace"]
        self.spec = c
        self.L = L
        self.topk = c["index_topk"]
        self.Hi, self.Di, self.H = Hi, Di, H
        self.r, self.lat = r, r + rope
        attn = (d * ql + ql * H * (nope + rope) + d * r + d * rope
                + H * nope * r + H * r * v + H * v * d)
        index = d * Hi * Di + d * Di + d * Hi
        dense = 3 * d * c["intermediate_size"]
        moe = d * E + 3 * d * fe * (k + c["n_shared_experts"])
        self.weight_macs = (L * (attn + index) + nd * dense
                            + (L - nd) * moe + c["vocab_size"] * d)
        # bytes of the bf16 weights a decode round streams: every layer's
        # held experts and the output head (not the embedding table, of
        # which a round reads B rows, nor the MTP module)
        held_moe = d * E + 3 * d * fe * (E + c["n_shared_experts"])
        self.weight_bytes = 2 * (L * (attn + index) + nd * dense
                                 + (L - nd) * held_moe + c["vocab_size"] * d)

    def token_flops(self, ctx: int) -> float:
        """FLOPs of one token that attends over ``ctx`` positions (its own
        included)."""
        sel = min(ctx, self.topk)
        per_layer = (2 * self.Hi * self.Di * ctx
                     + 2 * self.H * (self.lat + self.r) * sel)
        return 2.0 * self.weight_macs + self.L * per_layer

    def decode_round_bytes(self, lens: list[int], row_bytes: int) -> float:
        """Least bytes one decode round moves: the weights once, each
        slot's indexer keys over its live length (bf16), and ``index_topk``
        latent rows per (slot, layer) at the tier's bytes per row."""
        keys = sum(2 * self.Di * n for n in lens) * self.L
        rows = sum(min(n, self.topk) for n in lens) * self.L * row_bytes
        return self.weight_bytes + keys + rows


def peaks(path: str, device_kind: str) -> dict:
    """The peak table's entry for ``device_kind``; an unlisted device is
    an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]
