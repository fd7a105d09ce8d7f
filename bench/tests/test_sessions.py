"""A traffic generator that holds conversations needs no change to the
harness: a stub generator of two-turn sessions, put in place of the chat
mix, drives a whole CPU rehearsal, and each second turn is sent after the
first has finished, with the first turn's prompt and served tokens at the
head of its own prompt."""

import json
import sys
import types

import numpy as np

import run
from harness import files

SESSIONS = 4


def _make(params, seed, vocab, seconds):
    rng = np.random.default_rng(seed)
    return [{"due": 0.3 * i, "prompt": rng.integers(0, vocab, 24).tolist(),
             "max_tokens": 6, "temperature": 0.0, "seed": i, "turn": 0,
             "vocab": vocab} for i in range(SESSIONS)]


def _next_turn(params, spec, served):
    if spec["turn"] == 1:
        return None
    user = [(7 * i + 3) % spec["vocab"] for i in range(10)]
    return {**spec, "prompt": spec["prompt"] + list(served) + user,
            "turn": 1, "think_s": 0.2}


def test_two_turn_sessions_run_end_to_end(monkeypatch, capsys, tmp_path):
    stub = types.ModuleType("traffic.twoturn")
    stub.make, stub.next_turn = _make, _next_turn
    monkeypatch.setitem(sys.modules, "traffic.twoturn", stub)
    monkeypatch.setattr(files, "traffic", lambda name: {
        "generator": "twoturn", "after_window": "first_token"})
    dump = tmp_path / "records.json"
    rc = run.main(["--workload", "qwen2-0.5b.chat", "--seed", "4100000029",
                   "--seconds", "6", "--trace", "0", "--rehearse",
                   "--dump", str(dump)])
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] == 2 * SESSIONS
    recs = json.loads(dump.read_text())["recs"]
    first = recs[:SESSIONS]
    second = sorted(recs[SESSIONS:], key=lambda r: r["submit"])
    assert len(second) == SESSIONS
    assert all(r["prompt"] == 24 + 6 + 10 and len(r["times"]) == 6
               for r in second)
    # each second turn is sent a think time after some first turn ended
    ends = sorted(r["times"][-1] for r in first)
    for end, r in zip(ends, second):
        assert r["submit"] >= end + 0.2
