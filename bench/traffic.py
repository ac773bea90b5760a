"""The one traffic generator: a mix file of parameters in, requests out.

A closed-loop mix (``"kind": "closed_loop"``) keeps ``clients`` requests in
flight: a client sends its next request as soon as its previous one has
finished (zero think time). Requests are taken in order from one list, so
the order of admission is fixed by the list and the engine alone.

Lengths are lognormal, clipped, and drawn from the mix's own
``lengths_seed``: every run seed serves the same sizes in the same order,
so every seed runs the same set of compiled shapes. The run seed draws the
token ids (and, elsewhere, the weights). Prompt lengths are rounded up to a
whole number of ``prompt_granule`` tokens, which bounds the number of
distinct prefill shapes; output lengths are clipped so that prompt + output
stays below the engine's ``max_len``.

The length arithmetic (lognormal around a median, clipped) follows
``repro.serve.traffic``; the driver loop and the clock do not.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import numpy as np


def lognormal_lengths(rng: np.random.Generator, n: int, dist: Dict[str, Any],
                      granule: int = 1) -> np.ndarray:
    """n lengths, lognormal around ``median`` with log-sigma ``sigma``,
    clipped to [min, max] and rounded up to a multiple of ``granule``."""
    z = rng.standard_normal(n)
    raw = np.clip(dist["median"] * np.exp(dist["sigma"] * z),
                  dist["min"], dist["max"])
    return (np.ceil(raw / granule) * granule).astype(np.int64)


@dataclass
class ClosedLoopSchedule:
    """Request i of a closed-loop mix: ``prompt(i)`` and ``max_new[i]``."""

    mix: Dict[str, Any]
    seed: int
    vocab_size: int
    max_len: int

    def __post_init__(self):
        n = int(self.mix["requests"])
        rng = np.random.default_rng(int(self.mix["lengths_seed"]))
        self.prompt_len = lognormal_lengths(rng, n, self.mix["prompt"],
                                            self.granule)
        out = lognormal_lengths(rng, n, self.mix["output"])
        # the engine stops a sequence at max_len - 1 tokens
        self.max_new = np.minimum(out, self.max_len - 1 - self.prompt_len)
        assert (self.max_new >= 1).all(), "a prompt leaves no room to answer"

    @property
    def clients(self) -> int:
        return int(self.mix["clients"])

    @property
    def granule(self) -> int:
        return int(self.mix.get("prompt_granule", 1))

    def __len__(self) -> int:
        return len(self.prompt_len)

    def prompt(self, i: int) -> np.ndarray:
        # ids 2.. (0 and 1 are pad/eos by the data pipeline's convention)
        rng = np.random.default_rng([self.seed, i])
        return rng.integers(2, self.vocab_size, int(self.prompt_len[i]),
                            dtype=np.int32)

    def request(self, i: int) -> Tuple[np.ndarray, int]:
        return self.prompt(i), int(self.max_new[i])


def make_schedule(mix: Dict[str, Any], seed: int, vocab_size: int,
                  max_len: int):
    if mix["kind"] == "closed_loop":
        return ClosedLoopSchedule(mix, seed, vocab_size, max_len)
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")
