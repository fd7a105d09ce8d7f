"""A run with its timed path broken underneath must come out not correct;
the same run unbroken must come out correct.  CPU rehearsals of both cells
at the program's tiny sibling of each model (``run.py --rehearse``), so
the limit is the rehearsal's: the fp32 sibling against the fp32
reference."""

import json

import jax
import jax.numpy as jnp
import pytest

import run


def _token(eng):
    sample, V = eng._sample, eng.cfg.vocab_size
    eng._sample = lambda req, row: (sample(req, row) + V // 2) % V


def _state(eng):
    """Every step returns the state it was given: nothing is cached."""
    enqueue = eng.queue.enqueue

    def unchanged(kernel, params, arena, *rest):
        kept = jax.tree.map(jnp.copy, arena)
        logits, _ = enqueue(kernel, params, arena, *rest)
        return logits, kept
    eng.queue.enqueue = unchanged


def _half(eng):
    """The upper half of every batch gets the lower half's first answer."""
    launch = eng._launch

    def half(sd, chunk):
        rows, fed = launch(sd, chunk)
        rows = rows.copy()
        rows[len(rows) // 2:] = rows[0]
        return rows, fed
    eng._launch = half


CASES = [(cell, fault, fault is None)
         for cell in ("qwen2-0.5b.chat", "qwen2-0.5b.prefix",
                      "mamba2-780m.offline")
         for fault in (None, _token, _state, _half)]


@pytest.mark.parametrize("cell,fault,correct", CASES,
                         ids=[f"{c}-{f.__name__ if f else 'sound'}"
                              for c, f, _ in CASES])
def test_fault_is_caught(cell, fault, correct, capsys):
    rc = run.main(["--workload", cell, "--seed", "4100000017",
                   "--seconds", "5", "--trace", "0", "--rehearse"],
                  patch=fault)
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is correct, result["checks"]
    assert result["attempted"] > 0
