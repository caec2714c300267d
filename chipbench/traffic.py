"""The one traffic generator: a mix file of parameters in, requests out.

A mix is ``chipbench/traffic/<name>.json``; its ``kind`` picks one of
the generators in ``GENERATORS``.  Every seed gets the same work: the
lengths are fixed quantiles of the stated range, and the seed deals them
to rows and chooses the tokens.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class Planned:
    """One greedy request of the plan."""
    idx: int
    prompt: np.ndarray
    max_new: int


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def closed_batch(mix: dict, seed: int, vocab: int) -> List[Planned]:
    """``rows`` greedy requests present from the start, contexts spaced
    evenly over [min, max] and dealt to rows by the seed."""
    rng = np.random.default_rng(seed)
    rows, ctx = int(mix["rows"]), mix["context_tokens"]
    lens = np.rint(ctx["min"] + (np.arange(rows) + 0.5) / rows
                   * (ctx["max"] - ctx["min"])).astype(int)
    lens = lens[rng.permutation(rows)]
    return [Planned(idx=i,
                    prompt=rng.integers(0, vocab, int(n)).astype(np.int32),
                    max_new=int(mix["max_new_tokens"]))
            for i, n in enumerate(lens)]


GENERATORS = {"closed_batch": closed_batch}


def generate(mix: dict, seed: int, vocab: int) -> List[Planned]:
    kind = mix["kind"]
    if kind not in GENERATORS:
        raise ValueError(f"unknown traffic kind {kind!r}")
    return GENERATORS[kind](mix, seed, vocab)
