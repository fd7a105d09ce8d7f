"""The comparison that decides ``correct`` for a served model.

Once the window has closed and the program's state is freed, a sample of
the greedy requests the window finished, drawn from the seed and always
holding the longest, is run through the plain reference: one forward pass
over each prompt and its served tokens.  For every served token the gap
``max(reference logits) - reference logit of the served token`` is read;
the widest gap is the number compared.  A sound greedy server only picks a
token below the reference's best where the two nearly tie.

The reference's weights are made anew from the seed, by the same jitted
call that made the program's (``init_weights``), once the program's state
is freed: the reference reads nothing that the program holds.

The control is the reference computed in float8 put in the program's
place: at the same positions, the token that float8 puts first is scored
as if it had been served, through the same limit.  It has to come out not
correct.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import files
from reference.ops import logits_rows

PAD = 512          # sequences are padded to a multiple of this (causal)
ROWS = 256         # logits rows per block


def weights_key(seed: int):
    """The key every weight of a run is made from."""
    k = int(np.random.SeedSequence([seed, 1]).generate_state(1)[0])
    return jax.random.key(k)


def reference_weights(ref_name: str, s: dict, seed: int):
    ref = files.module("reference", ref_name)
    return jax.jit(lambda k: ref.init_weights(s, k))(weights_key(seed))


def sample(recs, n: int, seed: int):
    """Greedy requests that finished, the longest first, then a seeded
    sample of the others."""
    done = [r for r in recs if r.finish in ("length", "stop")
            and r.spec["temperature"] == 0.0 and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: -(len(r.spec["prompt"]) + len(r.tokens)))
    rest = done[1:]
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [done[0]] + [rest[i] for i in sorted(pick)]


@functools.lru_cache(maxsize=None)
def _hidden_fn(ref_name: str, s_items, mode: str):
    ref = files.module("reference", ref_name)
    s = dict(s_items)
    return jax.jit(lambda w, t: ref.hidden(s, w, t, mode))


@functools.lru_cache(maxsize=None)
def _logits_fn(mode: str):
    return jax.jit(lambda h, e: logits_rows(h, e, mode))


def output_head(ref_name: str, s: dict, w):
    """The ``(V, D)`` matrix the logits are read from: the reference's
    ``head(s, w)`` where it defines one (an untied head), else the
    embedding."""
    ref = files.module("reference", ref_name)
    return ref.head(s, w) if hasattr(ref, "head") else w["embed"]


def reference_logits(ref_name, s, w, prompt, tokens, mode="f32"):
    """Logits ``(len(tokens), V)`` that predict each served token."""
    seq = list(prompt) + list(tokens[:-1])
    S = -(-len(seq) // PAD) * PAD
    t = jnp.asarray(np.pad(np.asarray(seq, np.int32), (0, S - len(seq))))
    h = _hidden_fn(ref_name, tuple(sorted(s.items())), mode)(w, t)
    start, n = len(prompt) - 1, len(tokens)
    nb = -(-n // ROWS) * ROWS
    rows = jax.lax.dynamic_slice_in_dim(
        jnp.pad(h, ((0, nb), (0, 0))), start, nb, axis=0)
    fn, head = _logits_fn(mode), output_head(ref_name, s, w)
    out = [np.asarray(fn(rows[i:i + ROWS], head))
           for i in range(0, nb, ROWS)]
    return np.concatenate(out)[:n]


def gaps(ref_name, s, w, recs, control: bool = False) -> dict:
    """Widest gap over every scored token of ``recs``, with the count of
    tokens compared.  The scored tokens are the served ones, or with
    ``control`` the float8 reference's first choices at the same positions
    (the served tokens' own gap is then kept apart)."""
    worst = worst_served = 0.0
    n = 0
    for r in recs:
        ref = reference_logits(ref_name, s, w, r.spec["prompt"], r.tokens)
        rows = np.arange(len(r.tokens))
        best = ref.max(axis=1)
        served = float((best - ref[rows, np.asarray(r.tokens)]).max())
        worst_served = max(worst_served, served)
        if control:
            low = reference_logits(ref_name, s, w, r.spec["prompt"],
                                   r.tokens, "fp8")
            served = float((best - ref[rows, low.argmax(axis=1)]).max())
        worst = max(worst, served)
        n += len(rows)
        del ref
    out = {"logit_gap": worst, "tokens_compared": n}
    if control:
        out["served_logit_gap"] = worst_served
    return out
