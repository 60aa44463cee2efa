"""DeepSeek-V3.2-Exp (``model_type`` ``deepseek_v32``): the published keys
the program takes, the plain reference and the operation counts.

``KEY_MAP`` adds the architecture's keys to ``bench/config_map.py``'s
``COMMON`` table: MLA, the DSA indexer and the MoE.  ``NOT_TAKEN`` names
the published keys the program does not take yet (the configuration
file's ``departures``); the configuration tests hold each file of this
``model_type`` to it.  The weights are the program's layout, so the module
adds nothing to ``bench/weights.py``'s leaf tables.

``Reference`` is the layer equations in float32 ``jax.numpy``
(``bench/reference.py``'s ``Base``).  It follows the configuration file's
``departures`` (the points where the served program is not yet the
published model): the router covers only the experts held here, plain
RoPE (halves rotated) stands in for yarn, the indexer scores
``sum_h w_h relu(q_h . k)`` from the layer's normed hidden, and RMSNorm
gains are stored as ``1 + w``.  Per layer, every position's latent row
(``rmsnorm(c_kv) ++ rope(k_pe)``) and indexer key are computed first; then
the queries are processed in blocks of rows: exact causal top-
``index_topk`` over the indexer scores, MLA over the selected latent rows,
and the dense or MoE feed-forward.

``Model`` counts model FLOPs, each multiply-add as 2, and only the work the
model needs: every weight product of a token once (the
``num_experts_per_tok`` routed experts of those held here, the shared
expert, the output head), the indexer over the live context, and MLA over
at most ``index_topk`` selected rows (absorbed form: 576-wide scores,
512-wide values).  Work the program does beyond that (scoring padding, the
MTP module it does not serve) does not count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import F32, HI, NEG, Base, _q8, topk_select

# published key -> (field path in the program's ArchConfig, is a width)
KEY_MAP: dict[str, tuple[str, bool]] = {
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
    "index_topk": ("dsa.index_topk", False),
    "n_routed_experts": ("moe.num_experts", False),
    "n_shared_experts": ("moe.num_shared", False),
    "first_k_dense_replace": ("moe.first_dense_layers", False),
    "routed_scaling_factor": ("moe.routed_scale", False),
    "norm_topk_prob": ("moe.norm_topk", False),
    "num_nextn_predict_layers": ("mtp_depth", False),
    "n_group": ("moe.n_group", False),
    "topk_group": ("moe.topk_group", False),
    "topk_method": ("moe.topk_method", False),
    "scoring_func": ("moe.scoring_func", False),
    "moe_layer_freq": ("moe.layer_freq", False),
    "ep_size": ("moe.ep_size", False),
}
# published keys the program does not take yet: the grouped router's
# settings, its scoring function, yarn and the declared context
NOT_TAKEN = ("n_group", "topk_group", "topk_method", "rope_scaling",
             "scoring_func", "max_position_embeddings")


class Reference(Base):
    """Reference forward of one configuration over one weight tree."""

    def __init__(self, cfg: dict, params, max_len: int, *, block: int = 128,
                 lowp: bool = False):
        super().__init__(cfg, params, max_len, block=block, lowp=lowp)
        self.nd = int(cfg["first_k_dense_replace"])
        self.L = int(cfg["num_hidden_layers"])
        self._prep = jax.jit(self._prep_fn)
        self._blk = {moe: jax.jit(functools.partial(self._block_fn, moe=moe),
                                  donate_argnums=(1,))
                     for moe in (False, True)}

    def _rope(self, x, pos):
        half = x.shape[-1] // 2
        freqs = 1.0 / (float(self.c["rope_theta"])
                       ** (jnp.arange(half, dtype=F32) / half))
        ang = pos.astype(F32)[:, None] * freqs
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        if x.ndim == 3:
            cos, sin = cos[:, None], sin[:, None]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def _prep_fn(self, stack, i, x, pos):
        """Latent rows [S, kv_lora + rope] and indexer keys [S, Di] of all
        positions of layer ``i``."""
        lp = self._layer(stack, i)
        m = lp["mla"]
        h = self._norm(lp["ln1"], x)
        ckv = self._norm(m["kv_norm"], self._lin(h, m["w_dkv"], "sd,dr->sr"))
        kpe = self._rope(self._lin(h, m["w_kr"], "sd,dr->sr"), pos)
        ik = self._lin(h, lp["indexer"]["w_ik"], "sd,dk->sk")
        return jnp.concatenate([ckv, kpe], -1), ik

    def _mlp(self, p, h):
        g = self._lin(h, p["wi_gate"], "qd,df->qf")
        u = self._lin(h, p["wi_up"], "qd,df->qf")
        return self._lin(jax.nn.silu(g) * u, p["wo"], "qf,fd->qd")

    def _moe(self, p, h):
        c = self.c
        logits = jnp.einsum("qd,de->qe", h, p["router"].astype(F32),
                            precision=HI)
        gates = jax.nn.sigmoid(logits)
        sel = gates + p["router_bias"].astype(F32)[None]
        E = gates.shape[-1]
        k = min(int(c["num_experts_per_tok"]), E)
        _, ids = jax.lax.top_k(sel, k)
        w = jnp.take_along_axis(gates, ids, -1)
        if c["norm_topk_prob"]:
            w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-20)
        w = w * float(c["routed_scaling_factor"])
        dense_w = jnp.zeros_like(gates).at[
            jnp.arange(h.shape[0])[:, None], ids].set(w)          # [q,E]
        g = self._lin(h, p["w_gate"], "qd,edf->qef")
        u = self._lin(h, p["w_up"], "qd,edf->qef")
        hid = jax.nn.silu(g) * u
        if self.lowp:
            hid = _q8(hid, -1)
        out = jnp.einsum("qef,efd->qed", hid,
                         (_q8(p["w_down"].astype(F32), None) if self.lowp
                          else p["w_down"].astype(F32)),
                         precision=HI)
        y = jnp.einsum("qe,qed->qd", dense_w, out, precision=HI)
        return y + self._mlp(p["shared"], h)

    def _block_fn(self, stack, x, lat, ik, i, off, *, moe):
        """Layer ``i`` for query rows [off, off + block) of ``x``."""
        c = self.c
        lp = self._layer(stack, i)
        m, ix = lp["mla"], lp["indexer"]
        B = self.block
        xb = jax.lax.dynamic_slice_in_dim(x, off, B)
        pos = off + jnp.arange(B)
        h = self._norm(lp["ln1"], xb)
        # indexer: exact causal top-K over every earlier position
        iq = self._lin(h, ix["w_iq"], "qd,dhk->qhk")
        iw = self._lin(h, ix["w_iw"], "qd,dh->qh")
        dots = jnp.einsum("qhk,sk->qhs", iq, ik, precision=HI)
        sc = jnp.einsum("qh,qhs->qs", iw, jax.nn.relu(dots), precision=HI)
        valid = jnp.arange(self.S)[None] <= pos[:, None]
        ids, ok = topk_select(sc, valid, min(int(c["index_topk"]), self.S))
        rows = lat[ids]                                              # [q,K,D]
        # MLA, weights absorbed into the query (same math as k = c_kv W_uk)
        r = int(c["kv_lora_rank"])
        nope = int(c["qk_nope_head_dim"])
        cq = self._norm(m["q_norm"], self._lin(h, m["w_dq"], "qd,dl->ql"))
        q = self._lin(cq, m["w_uq"], "ql,lhk->qhk")
        q_nope, q_pe = q[..., :nope], self._rope(q[..., nope:], pos)
        q_lat = jnp.einsum("qhk,lhk->qhl", q_nope, m["w_uk"].astype(F32),
                           precision=HI)
        s = (jnp.einsum("qhl,qkl->qhk", q_lat, rows[..., :r], precision=HI)
             + jnp.einsum("qhe,qke->qhk", q_pe, rows[..., r:], precision=HI))
        s = s * (nope + int(c["qk_rope_head_dim"])) ** -0.5
        s = jnp.where(ok[:, None], s, NEG)
        pr = jax.nn.softmax(s, -1)
        o_lat = jnp.einsum("qhk,qkl->qhl", pr, rows[..., :r], precision=HI)
        o = jnp.einsum("qhl,lhv->qhv", o_lat, m["w_uv"].astype(F32),
                       precision=HI)
        xb = xb + self._lin(o, m["wo"], "qhv,hvd->qd")
        h2 = self._norm(lp["ln2"], xb)
        xb = xb + (self._moe(lp["ffn"], h2) if moe
                   else self._mlp(lp["ffn"], h2))
        return jax.lax.dynamic_update_slice_in_dim(x, xb, off, 0)

    def hidden(self, tokens: np.ndarray, first: int) -> jax.Array:
        """Final-layer hidden [n - first, d] (before the final norm) of
        positions first..n-1 of ``tokens`` (1-D, n <= max_len)."""
        n = int(tokens.shape[0])
        if n > self.S:
            raise ValueError(f"{n} tokens > reference buffer {self.S}")
        toks = np.zeros(self.S, np.int32)
        toks[:n] = tokens
        p = self.p
        x = p["embed"][jnp.asarray(toks)].astype(F32)
        pos = jnp.arange(self.S, dtype=jnp.int32)
        nb = -(-n // self.block)
        for li in range(self.L):
            moe = li >= self.nd
            stack, i = (p["layers"], li - self.nd) if moe \
                else (p["dense_layers"], li)
            lat, ik = self._prep(stack, i, x, pos)
            b0 = first // self.block if li == self.L - 1 else 0
            for b in range(b0, nb):
                x = self._blk[moe](stack, x, lat, ik, i, b * self.block)
            del lat, ik
        return x[first:n]


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
