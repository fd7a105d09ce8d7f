"""The general request generator: independent requests, open-loop.

A traffic file's ``traffic`` block gives its parameters:

* ``arrival``: ``"poisson"`` at ``rate_per_s`` over the window, or
  ``"backlog"``: ``requests`` requests all due at the window's start.
* ``prompt`` / ``output``: token-length distributions, each
  ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
  ``{"dist": "uniform", "min", "max"}`` (bounds inclusive).
* ``temperature``, and ``greedy_every``: within each block, the requests
  whose output length ranks 0, n, 2n, ... are greedy (temperature 0), so
  that the served tokens can be checked and the shortest is always among
  them.
* ``block`` (default: all requests): lengths are drawn per block of this
  many consecutive requests.  A backlog served by 64 slots takes blocks of
  64, so that whichever requests a window reaches hold the same lengths.

* ``order_seed``: when given, the order of the lengths and gaps comes
  from it and not from the run's seed.

Every seed gets the same work: lengths are the distribution's stratified
quantiles and Poisson gaps the exponential's, so the count of requests,
the multiset of lengths in every block and of gaps are fixed by the file
and the window.  The seed chooses the token ids and the sampling seeds,
and the order of lengths and gaps unless ``order_seed`` fixes it: which
request meets which in the batch changes the work a window does, so the
mixes fix it.
"""

from __future__ import annotations

from statistics import NormalDist

import numpy as np


def _lengths(d: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    if d["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = d["median"] * np.exp(d["sigma"] * z)
    elif d["dist"] == "uniform":
        v = d["min"] + u * (d["max"] + 1 - d["min"])
    else:
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    return np.clip(np.floor(v), d["min"], d["max"]).astype(int)


def make(params: dict, seed: int, vocab: int, seconds: float) -> list:
    """Requests of one run: dicts with ``due`` (seconds from the window's
    start), ``prompt``, ``max_tokens``, ``temperature`` and ``seed``."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(params["order_seed"]) \
        if "order_seed" in params else rng
    if params["arrival"] == "poisson":
        rate = params["rate_per_s"]
        n = max(1, int(round(rate * seconds)))
        u = (np.arange(n) + 0.5) / n
        gaps = order.permutation(-np.log1p(-u) / rate)
        due = np.cumsum(gaps)
        due *= seconds * n / (n + 1) / due[-1]    # all n inside the window
    elif params["arrival"] == "backlog":
        n = params["requests"]
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrival process {params['arrival']!r}")
    block = params.get("block", n)
    every = params.get("greedy_every", 0)
    prompts, outputs, greedy = [], [], []
    for start in range(0, n, block):
        m = min(block, n - start)
        prompts += list(order.permutation(_lengths(params["prompt"], m)))
        outs = order.permutation(_lengths(params["output"], m))
        rank = np.argsort(np.argsort(outs, kind="stable"), kind="stable")
        outputs += list(outs)
        greedy += [bool(every) and r % every == 0 for r in rank]
    out = []
    for i in range(n):
        out.append({
            "due": float(due[i]),
            "prompt": rng.integers(0, vocab, size=int(prompts[i])).tolist(),
            "max_tokens": int(outputs[i]),
            "temperature": 0.0 if greedy[i] else float(params["temperature"]),
            "seed": int(rng.integers(0, 2 ** 31)),
        })
    return out

