"""The one traffic generator: a mix file of parameters -> requests.

A mix (``traffic/<name>.json``) is data only.  Every mix is a closed
loop: ``clients`` requests are in flight, each replaced the moment it
finishes, from a pool drawn before the window opens.

    clients      requests in flight
    pool         requests drawn (rounded up to whole blocks of ``strata``)
    prompt, output   length distributions: {"dist": "lognormal", "median",
                 "sigma", "min", "max"}, {"dist": "uniform", "min", "max"}
                 or {"dist": "choice", "values": [...]}
    strata       requests per block (below)
    base_seed    seed of the lengths and their order

Every run seed gets the SAME lengths in the SAME order, drawn from the
mix's ``base_seed``; the run seed draws the token ids (and, elsewhere,
the weights).  A window holds only the first few requests of a pool (a
prefill of 32 positions takes a tick of 0.65 s in the chat cell, an SC
position 2 s), so a seed that reordered the lengths would change the
work inside the window, and the spread between runs would be the
draw's, not the system's.  The order is stratified: each quantity's
values are split into ``strata`` quantile ranges, and every block of
``strata`` consecutive requests holds one value of each range, so the
first requests are a fair sample of the mix.  Token ids avoid 0-2 (pad
and the usual special ids).
"""

from __future__ import annotations

import dataclasses

import numpy as np

LOW_ID = 3


@dataclasses.dataclass
class Item:
    prompt: list
    max_new: int


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    kind = spec["dist"]
    if kind == "lognormal":
        v = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
        v = np.clip(np.rint(v), spec["min"], spec["max"])
    elif kind == "uniform":
        v = rng.integers(spec["min"], spec["max"] + 1, size=n)
    elif kind == "choice":
        vals = np.asarray(spec["values"])
        v = vals[np.arange(n) % len(vals)]      # each value equally often
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return v.astype(np.int64)


def stratified(values: np.ndarray, k: int, rng) -> np.ndarray:
    """``values`` reordered so that every block of ``k`` holds one value
    of each of ``k`` quantile ranges: which value of a range goes to
    which block, and the order inside a block, are drawn from ``rng``.
    ``len(values)`` is a multiple of ``k``."""
    n = len(values)
    strata = np.sort(values).reshape(k, n // k)          # row j: range j
    strata = np.stack([row[rng.permutation(n // k)] for row in strata])
    blocks = strata.T                                    # block b: column b
    return np.stack([b[rng.permutation(k)] for b in blocks]).reshape(-1)


def count(mix: dict) -> int:
    """Requests a run draws: the pool, rounded up to whole blocks."""
    k = int(mix.get("strata", 1))
    return max(1, -(-int(mix["pool"]) // k)) * k


def generate(mix: dict, seed: int, vocab: int) -> list:
    """The run's requests, in submission order."""
    k = int(mix.get("strata", 1))
    n = count(mix)
    base = np.random.default_rng(mix["base_seed"])
    prompts = stratified(_lengths(mix["prompt"], n, base), k, base)
    outputs = stratified(_lengths(mix["output"], n, base), k, base)
    run = np.random.default_rng(seed)
    return [Item(prompt=run.integers(LOW_ID, vocab, size=int(p)).tolist(),
                 max_new=int(o))
            for p, o in zip(prompts, outputs)]
