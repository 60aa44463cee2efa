"""Operations and bytes of the served model, from the configuration file,
and the chip's peaks.

What a model's token costs depends on its architecture: ``Model`` is the
count of the file's ``model_type`` (``bench/archs/<model_type>.py``).
``peaks`` reads ``bench/peaks.json``.
"""

from __future__ import annotations

import json

import config_map


def Model(spec: dict):
    """Per-token operation counts of one configuration file: the ``Model``
    of its architecture's module ``bench/archs/<model_type>.py``, with
    ``weight_macs``, ``weight_bytes``, ``token_flops(ctx)`` and
    ``decode_round_bytes(lens, row_bytes)``."""
    return config_map.arch_module(spec).Model(spec)


def peaks(path: str, device_kind: str) -> dict:
    """The peak table's entry for ``device_kind``; an unlisted device is
    an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]
