"""Seeded random weights for the served model, made by the benchmark.

One jitted call draws every leaf on the device, in the dtype the program
serves it in, from the run's seed.  The tree's structure and leaf shapes
are the program's (``abstract`` is its abstract parameter tree); the
values and their scales are the benchmark's own, so the reference can
read the same arrays without taking anything the program made.

Scales: every projection has unit-variance outputs for unit-variance
inputs (std = 1 / sqrt(contracted size)); the embedding has unit
variance; the output head 1 / sqrt(hidden); norm gains (stored as
``1 + w``) and the router bias are zero.

The leaf names below are the program's.  An architecture module
(``bench/archs/<model_type>.py``) whose weights the program lays out
otherwise adds to them by defining tuples of the same names: ``STACKS``
(top-level groups whose leaves carry a leading layer dimension), ``ZERO``,
``CONTRACT_FIRST`` and ``EXPERT``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

STACKS = ("dense_layers", "layers", "mtp")
ZERO = ("ln1", "ln2", "ln_h", "ln_e", "q_norm", "kv_norm", "final_norm",
        "router_bias")
# leaves whose contracted dims are not "all but the last"
CONTRACT_FIRST = ("w_uq", "w_uk", "w_uv", "w_iq")   # [in, heads, dim]
EXPERT = ("w_gate", "w_up", "w_down")          # [experts, in, out]


def key_for(seed: int) -> jax.Array:
    """A threefry key holding all 64 bits of ``seed``."""
    s = seed % 2 ** 64
    return jax.random.wrap_key_data(
        jnp.asarray([s >> 32, s & 0xFFFFFFFF], jnp.uint32))


def leaf_std(path: tuple, shape: tuple, arch) -> float | None:
    """Std of a leaf's draw, or None for a zero leaf; ``arch`` is the
    architecture module, whose tables add to this module's."""
    def names_of(table: str, base: tuple) -> tuple:
        return base + tuple(getattr(arch, table, ()))

    names = [getattr(k, "key", str(k)) for k in path]
    name = names[-1]
    if name in names_of("ZERO", ZERO):
        return None
    if name == "embed":
        return 1.0
    s = shape[1:] if names[0] in names_of("STACKS", STACKS) else shape
    if name == "unembed":
        return 1.0 / math.sqrt(s[-1])
    if name in names_of("CONTRACT_FIRST", CONTRACT_FIRST):
        fan = s[0]
    elif name in names_of("EXPERT", EXPERT):
        fan = s[1]
    else:
        fan = int(np.prod(s[:-1]))
    return 1.0 / math.sqrt(fan)


def make(abstract, seed: int, arch):
    """Materialize ``abstract`` (a tree of ShapeDtypeStructs) from
    ``seed`` in one jitted call; ``arch`` is the architecture module
    (``config_map.arch_module``)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    plan = [(leaf_std(path, tuple(a.shape), arch), a.shape, a.dtype)
            for path, a in leaves]

    @jax.jit
    def draw(key):
        out = []
        for i, (std, shape, dtype) in enumerate(plan):
            if std is None:
                out.append(jnp.zeros(shape, dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * std).astype(dtype))
        return out

    return jax.tree.unflatten(treedef, draw(key_for(seed)))
