"""The one traffic generator: turns a mix file of ``bench/traffic`` and a
seed into a list of requests.

A mix is data only.  Its ``kind`` says how the run submits it;
``closed`` is the one kind so far: ``requests`` requests submitted
together at start (an offline batch), whose window opens once all of
them decode.  Lengths come
from ``prompt`` / ``output`` as ``{"dist": "fixed", "tokens": n}``, so
every seed serves the same sizes; the seed draws the token ids.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Req:
    index: int
    prompt: np.ndarray          # [prompt_len] int32 token ids
    max_tokens: int

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


def _tokens(spec: dict) -> int:
    if spec["dist"] != "fixed":
        raise ValueError(f"unknown length dist {spec['dist']!r}")
    return int(spec["tokens"])


def generate(mix: dict, seed: int, vocab: int) -> list[Req]:
    """Requests of one run: ``mix["requests"]`` prompts of token ids drawn
    from ``seed`` over the whole vocabulary."""
    if mix["kind"] != "closed":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    rng = np.random.default_rng([seed % 2 ** 64, 2])
    plen, olen = _tokens(mix["prompt"]), _tokens(mix["output"])
    return [Req(i, rng.integers(0, vocab, plen, np.int32), olen)
            for i in range(int(mix["requests"]))]
