"""Logical-axis sharding rules + activation-constraint context.

Model code never names mesh axes directly.  It calls ``shard(x, "batch",
None, "embed")`` with *logical* axes; the active :class:`ShardingCtx`
(a context manager installed by the launcher / dry-run) maps those to mesh
axes and applies ``with_sharding_constraint``.  Outside any context this is
an exact no-op, so unit tests and CPU smoke tests never touch device state.

Two built-in rule profiles:

* ``tp``  — tensor-parallel weights over ``model``; weights replicated over
  ``data``; activations batch-sharded over (``pod``, ``data``).
* ``2d``  — additionally shards the non-TP weight dim over ``data``
  (FSDP/ZeRO-3 style weight gathering, needed for >=100B params on 16 GB
  chips).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.params import axes_to_pspec

_STATE = threading.local()


# Logical activation axes: batch, seq (sequence-parallel for long ctx),
# heads/kv/ff/embed/vocab/experts follow the parameter logical axes.
def rules_tp(multi_pod: bool, *, seq_data: bool = False) -> dict[str, Any]:
    data = ("pod", "data") if multi_pod else ("data",)
    r = {
        "batch": data, "heads": "model", "kv": "model", "ff": "model",
        "vocab": "model", "experts": "model",
        # cache-tier batch axis: never unmapped (weights-stationary
        # profiles unmap "batch" for activations, but caches/host tiers
        # must stay batch-parallel)
        "cache_batch": data,
        # Megatron-style sequence parallelism: the residual stream between
        # blocks shards its seq dim over the model axis (all-gather before
        # attention/mlp, reduce-scatter after — inserted by the partitioner)
        "seq_sp": "model",
        # per-head dims / embed stay unsharded for tp profile
    }
    if seq_data:
        # long-context: batch too small to shard -> shard sequence over data
        r["seq"] = data
        r["batch"] = None
    return r


def rules_2d(multi_pod: bool, *, seq_data: bool = False) -> dict[str, Any]:
    r = rules_tp(multi_pod, seq_data=seq_data)
    data = ("pod", "data") if multi_pod else ("data",)
    # FSDP-style: shard the "long" replicated weight dims over the data axis.
    r.update({"embed": data, "ff2": "model"})
    return r


def rules_2d_ws(multi_pod: bool, *, seq_data: bool = False) -> dict[str, Any]:
    """Weights-stationary decode variant of ``2d``.

    Decode moves ~KB of activations but the ``2d`` profile's weight
    gathers move GBs per step.  Mapping the *activation* hidden dim onto
    the data axis aligns activations with the weights' data-sharded
    contraction dim, so matmuls run where the weights live and only tiny
    activation partial-sums cross the network (§Perf iteration 1).
    Batch stays on the data axis for cache-side ops (attention); XLA
    inserts the cheap activation reshards between the two regimes.
    """
    r = rules_2d(multi_pod, seq_data=seq_data)
    data = ("pod", "data") if multi_pod else ("data",)
    # activations vacate the data axis for their hidden dim (weights-
    # stationary); caches keep batch over data via their explicit
    # mesh-axis annotations in launch/steps.py, so attention stays
    # batch-parallel while matmuls stay weight-local.
    r["batch"] = None
    r["embed_act"] = data
    return r


PROFILES = {"tp": rules_tp, "2d": rules_2d, "2d_ws": rules_2d_ws}


def prune_spec(spec: P, shape: tuple[int, ...],
               mesh: jax.sharding.Mesh) -> P:
    """Drop mesh axes whose product doesn't divide the dim size (e.g. 8 kv
    heads on a 16-wide model axis): keeps the largest divisible prefix."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for d, entry in enumerate(tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        keep: list[str] = []
        prod = 1
        for a in axes:
            if shape[d] % (prod * sizes[a]) == 0:
                keep.append(a)
                prod *= sizes[a]
        if not keep:
            out.append(None)
        elif len(keep) == 1:
            out.append(keep[0])
        else:
            out.append(tuple(keep))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


class ShardingCtx:
    def __init__(self, mesh: jax.sharding.Mesh, rules: dict[str, Any]):
        self.mesh = mesh
        self.rules = dict(rules)

    def pspec(self, *axes: str | None) -> P:
        return axes_to_pspec(axes, self.rules)

    def sharding(self, *axes: str | None, memory_kind: str | None = None) -> NamedSharding:
        kw = {"memory_kind": memory_kind} if memory_kind else {}
        return NamedSharding(self.mesh, self.pspec(*axes), **kw)

    def sharding_for(self, shape: tuple[int, ...], axes,
                     memory_kind: str | None = None) -> NamedSharding:
        """Shape-aware: prunes mesh axes that don't divide the dims."""
        spec = prune_spec(self.pspec(*axes), shape, self.mesh)
        kw = {"memory_kind": memory_kind} if memory_kind else {}
        return NamedSharding(self.mesh, spec, **kw)


def current() -> ShardingCtx | None:
    return getattr(_STATE, "ctx", None)


@contextlib.contextmanager
def use_sharding(mesh: jax.sharding.Mesh | None, rules: dict[str, Any] | None):
    prev = getattr(_STATE, "ctx", None)
    _STATE.ctx = ShardingCtx(mesh, rules) if mesh is not None else None
    try:
        yield _STATE.ctx
    finally:
        _STATE.ctx = prev


def shard(x: jax.Array, *axes: str | None) -> jax.Array:
    """Constrain activation sharding by logical axes; no-op w/o context."""
    ctx = current()
    if ctx is None or ctx.mesh is None:
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"shard(): {len(axes)} axes for rank-{x.ndim} array")
    return jax.lax.with_sharding_constraint(
        x, ctx.sharding_for(x.shape, axes))


def logical_axis_size(name: str) -> int:
    """Product of mesh-axis sizes the logical axis maps to (1 w/o ctx)."""
    ctx = current()
    if ctx is None or ctx.mesh is None:
        return 1
    r = ctx.rules.get(name)
    if r is None:
        return 1
    axes = r if isinstance(r, tuple) else (r,)
    sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
    n = 1
    for a in axes:
        n *= sizes.get(a, 1)
    return n


def logical_sharding(*axes, memory_kind: str | None = None):
    """NamedSharding for the current ctx (None outside a context)."""
    ctx = current()
    if ctx is None or ctx.mesh is None:
        return None
    return ctx.sharding(*axes, memory_kind=memory_kind)
