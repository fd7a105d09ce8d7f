"""Multi-turn sessions that share prefixes: system prompts and the history
of each conversation, open-loop.

A traffic file's parameters:

* ``rate_per_s``: sessions per second, Poisson over the window (each
  session's first turn is due then).
* ``system``: ``count`` system prompts, their lengths a distribution as in
  ``requests.py``; ``zipf``: the prompt of popularity rank ``k`` (1 first)
  opens a share of the sessions proportional to ``k ** -zipf``.
* ``turns``: ``{"min", "max"}`` turns per session, uniform.
* ``user`` / ``output``: token-length distributions of each turn's new user
  tokens and of the tokens it asks for.
* ``think_s``: mean of the exponential pause between a turn's last token
  and the next turn's send.
* ``temperature``, and ``greedy_every``: every n-th turn, counted over all
  sessions' turns session by session, is greedy (temperature 0), so that
  served tokens can be checked.
* ``max_context``: a turn's prompt and its ``max_tokens`` stay within this
  many positions; a session whose next turn would not fit ends there.
* ``order_seed``: the order of every length, share and gap.
* ``rehearsal``: the values a CPU rehearsal takes instead (``run.py``).

Set-up serves each system prompt the window's sessions open on once
(``warm``), so that the window meets the prefix cache as a deployment that
has served these prompts before holds it, not cold.

A turn's prompt is the session's history (its system prompt, and every
earlier user turn and served answer) followed by the turn's new user
tokens, so every turn after the first can reuse the pages its session's
last turn filled, and every first turn its system prompt's.

Every seed gets the same work: the count of sessions, each session's
system prompt rank, turn count, lengths and think times are stratified
quantiles in an order fixed by ``order_seed``, as in ``requests.py``.  The
seed chooses the token ids and the sampling seeds.
"""

from __future__ import annotations

import numpy as np

from traffic.requests import _lengths, poisson_due


def _zipf_ranks(n: int, count: int, a: float) -> np.ndarray:
    """``n`` stratified draws of a rank in ``[0, count)``, rank ``k``
    weighted ``(k + 1) ** -a``."""
    w = np.arange(1, count + 1, dtype=float) ** -a
    u = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(np.cumsum(w) / w.sum(), u), count - 1)


def make(params: dict, seed: int, vocab: int, seconds: float) -> list:
    """The first turn of every session: dicts with ``due`` (seconds from
    the window's start), ``prompt``, ``max_tokens``, ``temperature`` and
    ``seed``, and the session's ``plan`` and ``turn`` for ``next_turn``."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(params["order_seed"])
    due = poisson_due(params["rate_per_s"], seconds, order)
    n = len(due)
    sysp = params["system"]
    system = [rng.integers(0, vocab, size=int(m)).tolist()
              for m in order.permutation(_lengths(sysp, sysp["count"]))]
    rank = order.permutation(_zipf_ranks(n, sysp["count"], params["zipf"]))
    turns = order.permutation(_lengths(
        {"dist": "uniform", **params["turns"]}, n))
    total = int(turns.sum())
    users = order.permutation(_lengths(params["user"], total))
    outputs = order.permutation(_lengths(params["output"], total))
    u = (np.arange(total - n) + 0.5) / (total - n)
    thinks = order.permutation(-np.log1p(-u) * params["think_s"])
    every = params.get("greedy_every", 0)
    out, j = [], 0
    for i in range(n):
        k = int(turns[i])
        t = range(j, j + k)
        plan = {
            "user": [rng.integers(0, vocab, size=int(users[x])).tolist()
                     for x in t],
            "output": [int(outputs[x]) for x in t],
            "temperature": [0.0 if every and x % every == 0
                            else float(params["temperature"]) for x in t],
            "seed": [int(rng.integers(0, 2 ** 31)) for _ in t],
            "think_s": [float(s) for s in thinks[j - i:j - i + k - 1]],
        }
        j += k
        out.append({"due": float(due[i]), "session": i,
                    "system": int(rank[i]), "turn": 0, "plan": plan,
                    "prompt": system[rank[i]] + plan["user"][0],
                    "max_tokens": plan["output"][0],
                    "temperature": plan["temperature"][0],
                    "seed": plan["seed"][0]})
    return out


def warm(params: dict, specs: list) -> list:
    """The system prompts that ``specs`` open on, one each, for set-up to
    serve before the window."""
    return list({s["system"]: s["prompt"][:-len(s["plan"]["user"][0])]
                 for s in specs}.values())


def next_turn(params: dict, spec: dict, served: list):
    """The session's next turn once ``spec`` has served ``served``, with
    ``think_s``, the pause before it is sent; None when the session ends
    (its turns are done, or the next would not fit ``max_context``)."""
    plan, k = spec["plan"], spec["turn"] + 1
    if k == len(plan["output"]):
        return None
    prompt = spec["prompt"] + list(served) + plan["user"][k]
    if len(prompt) + plan["output"][k] > params["max_context"]:
        return None
    return {"session": spec["session"], "system": spec["system"],
            "turn": k, "plan": plan, "prompt": prompt,
            "max_tokens": plan["output"][k],
            "temperature": plan["temperature"][k], "seed": plan["seed"][k],
            "think_s": plan["think_s"][k - 1]}
