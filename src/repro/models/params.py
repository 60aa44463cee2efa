"""Parameter definition system.

Pure-functional substitute for flax: every module describes its parameters as
a pytree of :class:`ParamDef` leaves (shape, dtype, initializer, *logical
axes*).  From one definition tree we derive

* ``init_params``      — materialized arrays (for real runs / smoke tests),
* ``abstract_params``  — ``jax.ShapeDtypeStruct`` stand-ins (for the dry-run;
  no allocation ever happens),
* ``param_pspecs``     — ``PartitionSpec`` tree via logical-axis → mesh-axis
  rules (the sharding side-channel used by ``jax.jit`` in/out shardings).

Logical axis names used across the model zoo:

``embed``   model width (d_model)            ``ff``      feed-forward width
``heads``   query heads                      ``kv``      kv heads
``qk``/``v`` per-head dims                   ``vocab``   vocabulary
``experts`` MoE expert dim                   ``layers``  stacked scan dim
``state``   SSM state dim                    ``conv``    conv channel dim
``lora``    MLA low-rank dims                ``idx``     DSA indexer dims
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ParamDef:
    """One parameter leaf: shape + dtype + init + logical sharding axes."""

    shape: tuple[int, ...]
    dtype: Any = jnp.float32
    init: str = "normal"          # normal | zeros | ones | embed | scaled
    axes: tuple[str | None, ...] = ()
    scale: float | None = None    # stddev override for "normal"/"scaled"

    def __post_init__(self):
        if self.axes and len(self.axes) != len(self.shape):
            raise ValueError(
                f"axes {self.axes} rank != shape {self.shape} rank")


def _fan_in(shape: tuple[int, ...]) -> int:
    # contraction dims are all but the last
    if len(shape) <= 1:
        return max(1, int(np.prod(shape[:-1])) if len(shape) else 1)
    return int(np.prod(shape[:-1]))


@functools.partial(jax.jit, donate_argnums=0)
def _scale_in_place(x: jax.Array, std: jax.Array) -> jax.Array:
    # the product reuses the draw's buffer, so a leaf's init peak is one
    # f32 copy plus its own bytes, not two f32 copies (a 129280x7168
    # embedding draws 3.7 GB in f32).  Same single multiply as the eager
    # product, so the values are bit-identical.
    return x * std


def _scaled_normal(key: jax.Array, d: ParamDef, std: float) -> jax.Array:
    x = jax.random.normal(key, d.shape, jnp.float32)
    return _scale_in_place(x, jnp.float32(std)).astype(d.dtype)


def materialize(key: jax.Array, d: ParamDef) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    if d.init == "embed":
        std = d.scale if d.scale is not None else 0.02
        return _scaled_normal(key, d, std)
    # normal / scaled: truncated-normal-ish fan-in scaling
    std = d.scale if d.scale is not None else 1.0 / math.sqrt(max(1, _fan_in(d.shape)))
    return _scaled_normal(key, d, std)


def is_def(x: Any) -> bool:
    return isinstance(x, ParamDef)


def init_params(key: jax.Array, defs: PyTree) -> PyTree:
    """Materialize a ParamDef tree into arrays (deterministic per-leaf keys)."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    keys = jax.random.split(key, len(leaves))
    vals = [materialize(k, d) for k, d in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, vals)


def abstract_params(defs: PyTree, mesh=None, rules: dict[str, str | tuple] | None = None,
                    memory_kind: str | None = None) -> PyTree:
    """ShapeDtypeStruct tree; optionally carries NamedShardings for dry-run."""
    def one(d: ParamDef):
        if mesh is not None:
            from repro.distributed.sharding import prune_spec
            spec = prune_spec(axes_to_pspec(d.axes, rules or {}), d.shape,
                              mesh)
            kw = {"memory_kind": memory_kind} if memory_kind else {}
            sh = jax.sharding.NamedSharding(mesh, spec, **kw)
            return jax.ShapeDtypeStruct(d.shape, d.dtype, sharding=sh)
        return jax.ShapeDtypeStruct(d.shape, d.dtype)
    return jax.tree.map(one, defs, is_leaf=is_def)


def axes_to_pspec(axes: Sequence[str | None], rules: dict[str, str | tuple]) -> P:
    """Map logical axes to a PartitionSpec under `rules`.

    A rule value may be a mesh axis name, a tuple of mesh axes, or None.
    Mesh axes already consumed by an earlier dim are dropped (a mesh axis may
    appear at most once in a PartitionSpec).
    """
    if not axes:
        return P()
    used: set[str] = set()
    out = []
    for ax in axes:
        r = rules.get(ax) if ax is not None else None
        if r is None:
            out.append(None)
            continue
        cand = r if isinstance(r, tuple) else (r,)
        keep = tuple(m for m in cand if m not in used)
        used.update(keep)
        if len(keep) == 0:
            out.append(None)
        elif len(keep) == 1:
            out.append(keep[0])
        else:
            out.append(keep)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_pspecs(defs: PyTree, rules: dict[str, str | tuple]) -> PyTree:
    return jax.tree.map(lambda d: axes_to_pspec(d.axes, rules), defs, is_leaf=is_def)


def stack_defs(defs: PyTree, n: int, axis_name: str | None = "layers") -> PyTree:
    """Add a leading stacked dim (for scan-over-layers parameter stacking)."""
    def one(d: ParamDef) -> ParamDef:
        return ParamDef(shape=(n,) + d.shape, dtype=d.dtype, init=d.init,
                        axes=(axis_name,) + (d.axes or (None,) * len(d.shape)),
                        scale=d.scale)
    return jax.tree.map(one, defs, is_leaf=is_def)


def count_params(defs: PyTree) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=is_def)
    return sum(int(np.prod(d.shape)) for d in leaves)


def param_bytes(defs: PyTree) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=is_def)
    return sum(int(np.prod(d.shape)) * jnp.dtype(d.dtype).itemsize for d in leaves)
