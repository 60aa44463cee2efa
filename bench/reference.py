"""Plain reference of the served model, and the comparison with it that
decides ``correct``.

``Reference(spec, params, max_len)`` is the reference forward of the
configuration file's architecture: the ``Reference`` class of its module
``bench/archs/<model_type>.py``, built on :class:`Base`.  Each is the
architecture's layer equations in float32 ``jax.numpy``, with no cache,
pool, paging or kernel; buffers are sized to a fixed ``max_len``, so one
compiled program serves every sequence length; every matrix product runs
at ``Precision.HIGHEST``.

``lowp=True`` gives the control: the same computation with every weight
product's operands rounded to float8 e4m3 (per-tensor scale for the
weights, per-row scale for the activations), one precision step below
the bfloat16 the configuration serves in.

What every architecture shares lives here: the exact top-k select
(:func:`topk_select`), the float8 rounding (:func:`_q8`), the weight
product, the RMSNorm and the logit-gap head (:class:`Base`), and
:func:`served_gaps`.

It imports nothing of the served program.  The weights it reads are the
benchmark's own (``bench/weights.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import config_map

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


class Base:
    """What every architecture's reference shares.  A subclass adds
    ``hidden(tokens, first)``: the final-layer hidden [n - first, d]
    (before the final norm) of positions first..n-1 of ``tokens``."""

    def __init__(self, cfg: dict, params, max_len: int, *, block: int = 128,
                 lowp: bool = False):
        self.c = cfg
        self.p = params
        self.block = block
        self.lowp = lowp
        self.S = -(-max_len // block) * block
        self._head = jax.jit(self._head_fn)

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

    def _layer(self, stack, i):
        return jax.tree.map(lambda a: a[i], stack)

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


def Reference(spec: dict, params, max_len: int, *, block: int = 128,
              lowp: bool = False) -> Base:
    """The reference forward of ``spec``'s architecture over one weight
    tree (the ``Reference`` of ``bench/archs/<model_type>.py``)."""
    return config_map.arch_module(spec).Reference(spec, params, max_len,
                                           block=block, lowp=lowp)


def served_gaps(ref: Base, prompt: np.ndarray, served: list[int], *,
                control: Base | None = None) -> dict:
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
