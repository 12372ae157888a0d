"""The one traffic generator: turns a mix's data file and a seed into work.

A mix is a JSON file under ``chipbench/traffic/``. Its ``"driver"`` key
names the entry point that serves it (``sweep`` or ``serve``); every other
key is a parameter read here. Numeric parameters that vary across tasks or
requests are *distributions*, written as objects:

* ``{"kind": "fixed", "value": v}``
* ``{"kind": "geometric", "base": b, "ratio": r, "offset": o}``:
  item ``i`` gets ``b * r ** (i + o)`` (a learning-rate ladder)
* ``{"kind": "loguniform", "low": a, "high": b}``
* ``{"kind": "lognormal", "median": m, "sigma": s, "low": a, "high": b}``
  (clipped to ``[low, high]``; ``"integer": true`` rounds)

Random draws are *stratified*: each block of ``BLOCK`` items holds the
same ``BLOCK`` quantiles of the distribution, in an order shuffled by the
seed. So every seed gets the same sizes in every
whole block and only the order changes, which keeps the work of a run the
same from seed to seed.

Token data is a copy of the repository's ``SyntheticLM`` stream (a pure
function of seed and step), so the yardstick does not move when the
program's data module does.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

#: items per stratified block of random draws
BLOCK = 16


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.Generator(np.random.Philox(key=seed,
                                                counter=[0, 0, 0, stream]))


def _quantile(dist: dict, u: float) -> float:
    kind = dist["kind"]
    if kind == "loguniform":
        lo, hi = math.log(dist["low"]), math.log(dist["high"])
        return math.exp(lo + u * (hi - lo))
    if kind == "lognormal":
        z = NormalDist().inv_cdf(u)
        v = dist["median"] * math.exp(dist["sigma"] * z)
        return min(max(v, dist["low"]), dist["high"])
    raise ValueError(f"distribution {kind!r} has no quantile")


def draw(dist: dict, n: int, seed: int, stream: int) -> List[float]:
    """``n`` values of ``dist`` for one seed (see the module docstring)."""
    kind = dist["kind"]
    if kind == "fixed":
        out = [dist["value"]] * n
    elif kind == "geometric":
        out = [dist["base"] * dist["ratio"] ** (i + dist.get("offset", 0))
               for i in range(n)]
    else:
        rng = _rng(seed, stream)
        qs = [_quantile(dist, (j + 0.5) / BLOCK) for j in range(BLOCK)]
        out = []
        while len(out) < n:
            out.extend(qs[j] for j in rng.permutation(BLOCK))
        out = out[:n]
    if dist.get("integer"):
        out = [int(round(v)) for v in out]
    return out


# --------------------------------------------------------------------- sweep
def sweep_tasks(mix: dict, seed: int) -> List[Dict]:
    """The sweep's queue: one dict per task, ``id``, ``lr``, ``seed``,
    ``steps``. Task ``i`` trains from seed ``seed + i``."""
    n = mix["tasks"]
    lrs = draw(mix["lr"], n, seed, 1)
    steps = draw(mix["steps"], n, seed, 2)
    return [{"id": i, "lr": float(lrs[i]), "seed": seed + i,
             "steps": int(steps[i])} for i in range(n)]


def lm_batch(vocab: int, seq: int, batch: int, seed: int, step: int
             ) -> Dict[str, np.ndarray]:
    """Next-token batch ``{"tokens", "labels"}`` (batch, seq) int32: a
    copy of ``repro.data.SyntheticLM.batch`` (uniform ids with an 8-token
    motif repeated every 32 positions, so the loss is learnable)."""
    rng = np.random.Generator(np.random.Philox(key=seed,
                                               counter=[step, 0, 0, 0]))
    raw = rng.integers(0, vocab, size=(batch, seq + 1), dtype=np.int64)
    rep = rng.integers(0, vocab, size=(batch, 8))
    for i in range(0, seq, 32):
        w = min(8, seq + 1 - i)
        raw[:, i:i + w] = rep[:, :w]
    return {"tokens": raw[:, :-1].astype(np.int32),
            "labels": raw[:, 1:].astype(np.int32)}


# --------------------------------------------------------------------- serve
def serve_requests(mix: dict, seed: int, vocab: int, n: int
                   ) -> List[Dict]:
    """``n`` requests of the backlog: ``id``, ``prompt`` (int32 ids,
    uniform over ``vocab``) and ``max_new``."""
    plen = draw(mix["prompt_len"], n, seed, 3)
    new = draw(mix["max_new"], n, seed, 4)
    rng = _rng(seed, 5)
    return [{"id": i,
             "prompt": rng.integers(0, vocab, size=(int(plen[i]),),
                                    dtype=np.int32),
             "max_new": int(new[i])} for i in range(n)]
