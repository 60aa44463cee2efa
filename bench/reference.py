"""Plain reference of the served model: DeepSeek-V3.2-Exp's layer
equations in float32 ``jax.numpy``, with no cache, pool, paging or kernel.

It reads its sizes from the configuration file (``bench/configs``) and
follows the file's ``departures`` (the points where the served program is
not yet the published model): the router covers only the experts held
here, plain RoPE (halves rotated) stands in for yarn, the indexer scores
``sum_h w_h relu(q_h . k)`` from the layer's normed hidden, and RMSNorm
gains are stored as ``1 + w``.

Per layer, every position's latent row (``rmsnorm(c_kv) ++ rope(k_pe)``)
and indexer key are computed first; then the queries are processed in
blocks of rows: exact causal top-``index_topk`` over the indexer scores,
MLA over the selected latent rows, and the dense or MoE feed-forward.
Buffers are sized to a fixed ``max_len``, so one compiled program serves
every sequence length.  Every matrix product runs at
``Precision.HIGHEST``.

``lowp=True`` gives the control: the same computation with every weight
product's operands rounded to float8 e4m3 (per-tensor scale for the
weights, per-row scale for the activations), one precision step below
the bfloat16 the configuration serves in.

It imports nothing of the served program.  The weights it reads are the
benchmark's own (``bench/weights.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
NEG = -3.0e38


def topk_select(sc, valid, k: int):
    """Exact top-``k`` of each row of ``sc`` [q, S] among ``valid``, with
    ``lax.top_k``'s ties (the lower position wins), without a sort.

    The k-th largest score is found bit by bit on an order-preserving
    uint32 image of the floats (32 counting passes over the row); every
    score above it is kept, and of those equal to it the first ones by
    position.  Returns ``(ids [q, k], ok [q, k])``: the kept positions in
    position order, and which of the k entries are real (a row with
    fewer than k valid scores keeps them all)."""
    bits = jax.lax.bitcast_convert_type(sc.astype(F32), jnp.uint32)
    neg = (bits >> 31) == 1
    u = jnp.where(neg, ~bits, bits | jnp.uint32(0x80000000))
    u = jnp.where(valid, u, jnp.uint32(0))

    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (u >= cand[:, None]).sum(-1) >= k
        return jnp.where(enough, cand, thr)

    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros(sc.shape[0], jnp.uint32))
    gt = valid & (u > thr[:, None])
    eq = valid & (u == thr[:, None])
    n_tie = k - gt.sum(-1, keepdims=True)
    keep = gt | (eq & (jnp.cumsum(eq, -1) <= n_tie))
    # the j-th kept position is where the running count of kept ones
    # first reaches j + 1 (a binary search per row, no scatter)
    cum = jnp.cumsum(keep, -1, dtype=jnp.int32)
    want = jnp.arange(1, k + 1, dtype=jnp.int32)
    ids = jax.vmap(lambda c: jnp.searchsorted(c, want, side="left"))(cum)
    ok = want[None] <= cum[:, -1:]
    ids = jnp.where(ok, ids, 0).astype(jnp.int32)
    return ids, ok


def _q8(t, axis):
    """Round to float8 e4m3 with an absmax scale over ``axis``."""
    s = jnp.max(jnp.abs(t), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (t / s).astype(jnp.float8_e4m3fn).astype(F32) * s


class Reference:
    """Reference forward of one configuration over one weight tree."""

    def __init__(self, cfg: dict, params, max_len: int, *, block: int = 128,
                 lowp: bool = False):
        self.c = cfg
        self.p = params
        self.block = block
        self.lowp = lowp
        self.S = -(-max_len // block) * block
        self.nd = int(cfg["first_k_dense_replace"])
        self.L = int(cfg["num_hidden_layers"])
        self._prep = jax.jit(self._prep_fn)
        self._blk = {moe: jax.jit(functools.partial(self._block_fn, moe=moe),
                                  donate_argnums=(1,))
                     for moe in (False, True)}
        self._head = jax.jit(self._head_fn)

    # -- pieces ---------------------------------------------------------------

    def _lin(self, x, w, spec):
        """A weight product ``einsum(spec, x, w)`` in f32 (control: e4m3
        operands)."""
        w = w.astype(F32)
        if self.lowp:
            x = _q8(x, -1)
            w = _q8(w, None)
        return jnp.einsum(spec, x, w, precision=HI, preferred_element_type=F32)

    def _norm(self, w, x):
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + float(self.c["rms_norm_eps"]))
        return x * (1.0 + w.astype(F32))

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

    def _layer(self, stack, i):
        return jax.tree.map(lambda a: a[i], stack)

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

    def _head_fn(self, norm, unembed, h, served, ref_h):
        """Logit gaps of one block of positions.  ``h`` is this model's
        final hidden, ``ref_h`` the float32 reference's (the same array
        when this is the reference).  Returns, per position, the gap by
        which the served token's reference logit lies below the
        reference's best, and the gap of the token this model puts first."""
        ref = jnp.einsum("qd,vd->qv", self._norm(norm, ref_h),
                         unembed.astype(F32), precision=HI)
        own = self._lin(self._norm(norm, h), unembed, "qd,vd->qv")
        best = ref.max(-1)
        got = jnp.take_along_axis(ref, served[:, None], 1)[:, 0]
        pick = jnp.argmax(own, -1)
        picked = jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
        return best - got, best - picked

    # -- entry points ---------------------------------------------------------

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

    def gaps(self, h: jax.Array, served: np.ndarray, ref_h: jax.Array
             ) -> tuple[np.ndarray, np.ndarray]:
        """Per-position gaps (see ``_head_fn``) in blocks of rows."""
        n = int(h.shape[0])
        out_s, out_p = [], []
        for a in range(0, n, self.block):
            b = min(n, a + self.block)
            pad = self.block - (b - a)
            hs = jnp.pad(h[a:b], ((0, pad), (0, 0)))
            rs = jnp.pad(ref_h[a:b], ((0, pad), (0, 0)))
            sv = jnp.asarray(np.pad(served[a:b], (0, pad)), jnp.int32)
            gs, gp = self._head(self.p["final_norm"], self.p["unembed"],
                                hs, sv, rs)
            out_s.append(np.asarray(gs)[:b - a])
            out_p.append(np.asarray(gp)[:b - a])
        return np.concatenate(out_s), np.concatenate(out_p)


def served_gaps(ref: Reference, prompt: np.ndarray, served: list[int], *,
                control: Reference | None = None) -> dict:
    """Compare one request's served tokens with the reference.

    The reference runs once over prompt + served tokens (teacher-forced);
    the logits at position ``len(prompt) - 1 + j`` predict served token
    ``j``.  Returns ``served_gap``, the widest gap of a served token below
    the reference's best.  With a ``control`` (a ``lowp`` Reference) the
    token the control puts first at each of those positions stands in the
    served token's place: ``served_gap`` is then the control's widest gap,
    and ``program_gap`` the served tokens'."""
    toks = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    first = int(prompt.shape[0]) - 1
    sv = np.asarray(served, np.int32)
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(toks, first)
        g_served, _ = ref.gaps(h, sv, h)
        out = {"served_gap": float(g_served.max()), "n": len(served)}
        if control is not None:
            hc = control.hidden(toks, first)
            _, g_ctl = control.gaps(hc, sv, h)
            out = {"served_gap": float(g_ctl.max()), "n": len(served),
                   "program_gap": out["served_gap"]}
    return out
