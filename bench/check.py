"""What decides `correct`: every completion has the length its request
asked for, in range; and on a sample of the window's requests, drawn from
the seed with the longest in it, the widest gap by which a served token's
reference logit lies below the reference's best stays under the cell's
limit (`cells/<workload>.json`).

The reference (bench/models/<family>_reference.py) runs after the
window, on weights drawn again from the seed, once the program's state is
freed; it reads nothing the program made but its served tokens.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import List, Sequence, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent


def limits(workload: str, root: Path = HERE) -> dict:
    with open(root / "cells" / f"{workload}.json") as f:
        return json.load(f)


def bad_completions(specs, done, vocab: int) -> int:
    """Requests whose completion is missing, of the wrong length, or holds
    an id outside the vocabulary (the cell's eos never fires, so every
    request runs to its max_new)."""
    bad = 0
    for spec, c in zip(specs, done):
        if c is None or len(c.tokens) != spec.max_new or \
                not all(0 <= t < vocab for t in c.tokens):
            bad += 1
    return bad + max(len(specs) - len(done), 0)


def sample(jobs: Sequence, n: int, seed: int) -> List[Tuple[int, int]]:
    """(job, request) pairs to compare: the last job's longest request
    (prompt plus served tokens) and n - 1 more drawn from the seed among
    all of the window's requests."""
    last = len(jobs) - 1
    specs = jobs[last].specs
    longest = max(range(len(specs)),
                  key=lambda i: (len(specs[i].prompt) + specs[i].max_new, -i))
    pool = [(j, i) for j, job in enumerate(jobs)
            for i in range(len(job.specs)) if (j, i) != (last, longest)]
    rng = np.random.default_rng([seed & 0xFFFFFFFFFFFF, 7])
    pick = rng.choice(len(pool), size=min(n - 1, len(pool)), replace=False)
    return [(last, longest)] + [pool[k] for k in sorted(pick)]


def rows(pairs, jobs, length: int):
    """tokens [R, length] (prompt, then the served tokens but the last)
    and targets [R, length] (the served token each position produced, or
    -1)."""
    tokens = np.zeros((len(pairs), length), np.int32)
    targets = np.full((len(pairs), length), -1, np.int32)
    for r, (j, i) in enumerate(pairs):
        prompt = jobs[j].specs[i].prompt
        served = jobs[j].done[i].tokens
        seq = list(prompt) + list(served[:-1])
        if len(seq) > length:
            raise ValueError(f"row of {len(seq)} tokens over {length}")
        tokens[r, :len(seq)] = seq
        targets[r, len(prompt) - 1:len(prompt) - 1 + len(served)] = served
    return tokens, targets


def row_length(mix: dict) -> int:
    """The reference's fixed row length for a mix: its longest prompt and
    output, rounded up to 128."""
    n = mix["prompt"]["max"] + mix["output"]["max"]
    return -(-n // 128) * 128


def report(checks: dict) -> str:
    """One line per number compared, beside its limit."""
    return "\n".join(f"check {k} value={v['value']} limit={v['limit']}"
                     for k, v in checks.items())
