"""From a configuration file of ``bench/configs`` to the program's config.

Each key of the published config names the field of the program's config
dataclasses it sets (``moe.top_k`` is field ``top_k`` of ``cfg.moe``).
``COMMON`` holds the keys every catalog entry shares; the architecture
module of the file's ``model_type`` (:func:`arch_module`) adds its own in
its ``KEY_MAP``, and its entry wins where both name a key.  A key whose field
the program's dataclasses lack today is reported as not taken; once a
later change adds the field under that name, the key is applied with no
edit here.  A width must equal the field it maps onto, or the run fails.
Keys absent from both tables are reported as not taken too.

A table may name a leaf of a nested group by dotted key
(``rope_parameters.rope_theta``).  A group with a mapped leaf is walked
and each unmapped leaf is reported by its dotted key; a group with none
is one key, as any other.  A JSON list reaches the program as a tuple and
a JSON object as a tuple of its sorted ``(key, value)`` pairs, so the
program's frozen config stays hashable.

``serve`` holds the engine settings that are not model keys: the
program's registered architecture (``arch``), the ESS options
(``serve.ess`` -> ``cfg.ess``) and the MTP depth served.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
from typing import Any

# the architecture modules, one per model_type: bench/archs/<model_type>.py
ARCHS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "archs")

# published key -> (field path in the program's ArchConfig, is a width)
COMMON: dict[str, tuple[str, bool]] = {
    "hidden_size": ("d_model", True),
    "num_attention_heads": ("num_heads", True),
    "num_key_value_heads": ("num_kv_heads", True),
    "vocab_size": ("vocab_size", False),
    "num_hidden_layers": ("num_layers", False),
    "rms_norm_eps": ("norm_eps", False),
    "rope_theta": ("rope_theta", False),
    "tie_word_embeddings": ("tie_embeddings", False),
    "attention_bias": ("qkv_bias", False),
    "hidden_act": ("act", False),
    "max_position_embeddings": ("max_position_embeddings", False),
    "rope_scaling": ("rope_scaling", False),
}
# keys of the file that describe it rather than the model
META = ("source", "published", "deployment", "departures", "serve",
        "assumed", "model_type")


@functools.lru_cache(maxsize=None)
def _load_arch(path: str):
    name = "bench_arch_" + os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def arch_module(spec: dict):
    """The module ``<ARCHS>/<model_type>.py`` of a configuration file: its
    ``KEY_MAP`` and ``NOT_TAKEN``, its ``Reference``
    (``bench/reference.py``), its ``Model`` (``bench/flops.py``) and any
    additions to ``bench/weights.py``'s leaf tables."""
    if "model_type" not in spec:
        raise KeyError("the configuration file has no model_type")
    path = os.path.join(ARCHS, spec["model_type"] + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"model_type {spec['model_type']!r}: no "
                                f"architecture module {path}")
    return _load_arch(path)


def key_map(spec: dict) -> dict[str, tuple[str, bool]]:
    """Published key -> (field path, is a width) for ``spec``'s
    architecture."""
    return {**COMMON, **arch_module(spec).KEY_MAP}


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


def frozen(value):
    """``value`` with every JSON list made a tuple and every JSON object a
    tuple of its sorted ``(key, value)`` pairs."""
    if isinstance(value, list):
        return tuple(frozen(v) for v in value)
    if isinstance(value, dict):
        return tuple(sorted((k, frozen(v)) for k, v in value.items()))
    return value


def _items(table: dict, key: str, value):
    """The ``(published key, value)`` pairs of one key of the file: a group
    with a leaf in ``table`` is walked by dotted key, anything else is one
    pair."""
    if isinstance(value, dict) and any(k.startswith(key + ".")
                                       for k in table):
        for sub, v in value.items():
            yield from _items(table, f"{key}.{sub}", v)
    else:
        yield key, value


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
    table = key_map(spec)
    not_taken = []
    for top, group in spec.items():
        if top in META:
            continue
        for key, value in _items(table, top, group):
            if key not in table:
                not_taken.append(key)
                continue
            path, width = table[key]
            new = _set(cfg, path, frozen(value), width, key)
            if new is None:
                not_taken.append(key)
            else:
                cfg = new
    ess = serve.get("ess")
    if ess:
        cfg = dataclasses.replace(
            cfg, ess=dataclasses.replace(cfg.ess, **ess))
    return Mapped(cfg, sorted(not_taken), int(serve.get("mtp_depth", 0)))
