"""The one traffic generator. A mix is a data file, `traffic/<mix>.json`,
read here; nothing of a mix lives in code.

Every seed gets the same request sizes in the same order: prompt and
output lengths are quantiles, at the stratified points (i + 0.5) / n, of
a lognormal with the published mean and the mix's sigma, clipped to the
mix's range, paired and then ordered by
permutations drawn from the mix's own `pairing_seed`. The run's `--seed`
draws the token ids (and the weights), so seeds change the content of the
work, not its amount or its schedule: in a closed batch the order of the
sizes sets the makespan.

A job is one closed batch: all its requests are due at once (the
program's `Server.serve` takes a closed queue and no arrival times).
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Spec:
    prompt: List[int]            # token ids
    max_new: int


def load(name: str, root: Path = HERE) -> dict:
    with open(root / "traffic" / f"{name}.json") as f:
        return json.load(f)


def lengths(dist: dict, n: int) -> np.ndarray:
    """n stratified quantiles of the lognormal of mean `mean` and shape
    `sigma` (median mean * exp(-sigma^2 / 2)), rounded and clipped to
    [min, max]."""
    median = dist["mean"] * math.exp(-dist["sigma"] ** 2 / 2)
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = [median * math.exp(dist["sigma"] * zi) for zi in z]
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)


def sizes(mix: dict) -> List[tuple]:
    """(prompt length, output length) of each request of a job, in the
    mix's fixed pairing and order."""
    n = mix["requests_per_job"]
    rng = np.random.default_rng(mix["pairing_seed"])
    p = lengths(mix["prompt"], n)
    o = lengths(mix["output"], n)[rng.permutation(n)]
    order = rng.permutation(n)
    return list(zip(p[order].tolist(), o[order].tolist()))


def job(mix: dict, vocab: int, seed: int) -> List[Spec]:
    """One job's requests for `seed`: the mix's sizes, with token ids drawn
    from the seed uniformly over the vocabulary."""
    rng = np.random.default_rng(seed)
    return [Spec(rng.integers(0, vocab, p).tolist(), o)
            for p, o in sizes(mix)]


def warmup(mix: dict, vocab: int) -> List[Spec]:
    """A short job that passes through every program shape the window
    uses: more requests than lanes (so lanes finish, free and refill)
    and a drain."""
    rng = np.random.default_rng(0)
    return [Spec(rng.integers(0, vocab, 3).tolist(), 2)
            for _ in range(mix["lanes"] + 1)]
