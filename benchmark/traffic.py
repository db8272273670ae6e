"""The one traffic generator. A mix is a data file under `traffic/`; this
turns (file, --seed, --seconds) into the work of one run.

Every seed gets the SAME set of lengths and the same set of gaps between
arrivals: lengths are the quantiles of the file's distribution on an even
grid, and so are the gaps of a Poisson process. Their ORDER is drawn from
the seed, or, where the file gives an `order_seed`, from that: a serving
window holds some tens of requests, and which long prompt meets which
burst then decides a tail more than any change to the program would, so
such a mix fixes its schedule and lets the seed draw the tokens (and the
weights).
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _rng(seed: int, salt: int):
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  salt])


def lengths(spec: dict, n: int) -> np.ndarray:
    """`n` lengths on the quantile grid of a clipped log-normal (or a
    constant), unshuffled."""
    if spec["dist"] == "constant":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    u = (np.arange(n) + 0.5) / n
    z = np.array([nd.inv_cdf(float(x)) for x in u])
    vals = np.exp(mu + sigma * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def poisson_gaps(rate: float, n: int) -> np.ndarray:
    """`n` exponential gaps on the quantile grid, mean 1/rate."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    return gaps * (n / rate) / gaps.sum()  # exact mean on a finite grid


def serve_requests(mix: dict, seed: int, seconds: float, vocab: int,
                   n: int | None = None) -> list:
    """[{"due": s, "prompt": [ids], "out": n}, ...] in arrival order.
    An open-loop mix (`arrivals.process == "poisson"`) gets
    floor(rate * seconds) requests, all due inside the window; a backlog
    gets `n` requests, all due at 0."""
    arr = mix["arrivals"]
    order = mix.get("order_seed", seed)
    if arr["process"] == "poisson":
        n = int(math.floor(arr["rate_per_s"] * seconds))
        gaps = poisson_gaps(arr["rate_per_s"], n)
        _rng(order, 1).shuffle(gaps)
        due = np.cumsum(gaps) - gaps[0] / 2.0
        due = np.minimum(due, seconds * (1 - 1e-9))
    elif arr["process"] == "backlog":
        if n is None:
            raise ValueError("a backlog needs its length")
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    cycle = min(mix.get("length_cycle") or n, n)
    p_len = _cycles(lengths(mix["prompt_len"], cycle), n, _rng(order, 2))
    o_len = _cycles(lengths(mix["output_len"], cycle), n, _rng(order, 3))
    tok = _rng(seed, 4)
    limit = mix.get("max_total")
    out = []
    for i in range(n):
        p, o = int(p_len[i]), int(o_len[i])
        if limit and p + o > limit:
            p = limit - o
        out.append({"due": float(due[i]),
                    "prompt": tok.integers(0, vocab, p).tolist(),
                    "out": o})
    return out


def _cycles(grid: np.ndarray, n: int, rng) -> np.ndarray:
    """The grid, reshuffled for every cycle, repeated up to `n` values."""
    out = []
    while sum(len(x) for x in out) < n:
        out.append(rng.permutation(grid))
    return np.concatenate(out)[:n]


def train_batches(mix: dict, seed: int, n: int, vocab: int) -> np.ndarray:
    """(n, 1, rows, seq + 1) token ids, uniform over the vocabulary: what
    `Trainer.train_step` takes as one step's `text`."""
    return _rng(seed, 5).integers(
        0, vocab, (n, 1, mix["rows_per_step"], mix["seq_length"] + 1),
        dtype=np.int32)
