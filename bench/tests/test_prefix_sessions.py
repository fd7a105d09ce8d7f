"""The session generator of the ``qwen2-0.5b.prefix`` cell: every seed gets
the same work, system prompts are shared and Zipf-ranked, each turn carries
its session's history within ``max_context``; and a CPU rehearsal of the
cell comes out correct, with every turn after the first reusing the pages
of its session's last turn and every first turn its system prompt's, which
set-up served."""

import collections
import json

import pytest

import run
from harness import files
from traffic import sessions

VOCAB = 151936
SECONDS = 51.0


def _work(specs):
    """What a run offers, apart from token ids and sampling seeds."""
    return [(s["due"], s["system"], len(s["prompt"]),
             [len(u) for u in s["plan"]["user"]], s["plan"]["output"],
             s["plan"]["temperature"], s["plan"]["think_s"]) for s in specs]


@pytest.fixture(scope="module")
def mix():
    return files.traffic("prefix")


def test_same_work_for_every_seed_and_same_requests_for_one(mix):
    a = sessions.make(mix, 1, VOCAB, SECONDS)
    b = sessions.make(mix, 2 ** 31 + 11, VOCAB, SECONDS)
    assert len(a) == round(mix["rate_per_s"] * SECONDS)
    assert _work(a) == _work(b)
    assert [s["prompt"] for s in a] != [s["prompt"] for s in b]
    assert sessions.make(mix, 1, VOCAB, SECONDS) == a
    assert max(s["due"] for s in a) < SECONDS
    turns = [len(s["plan"]["output"]) for s in a]
    assert {min(turns), max(turns)} == {mix["turns"]["min"],
                                        mix["turns"]["max"]}
    greedy = sum(t == 0.0 for s in a for t in s["plan"]["temperature"])
    assert greedy == -(-sum(turns) // mix["greedy_every"])


def test_system_prompts_are_shared_and_zipf_ranked(mix):
    specs = sessions.make(mix, 3, VOCAB, SECONDS)
    sysp = mix["system"]
    heads = {}
    for s in specs:
        user = len(s["plan"]["user"][0])
        heads.setdefault(s["system"], set()).add(tuple(s["prompt"][:-user]))
    assert all(len(h) == 1 for h in heads.values())
    lens = [len(next(iter(h))) for h in heads.values()]
    assert sysp["min"] <= min(lens) and max(lens) <= sysp["max"]
    count = collections.Counter(s["system"] for s in specs)
    ranked = [count[k] for k in range(sysp["count"])]
    assert ranked[0] == max(ranked) > ranked[-1]
    share = 1.0 / sum((k + 1) ** -mix["zipf"] for k in range(sysp["count"]))
    assert abs(ranked[0] / len(specs) - share) <= 1.0 / len(specs)


def test_next_turn_carries_the_history_within_max_context(mix):
    spec = max(sessions.make(mix, 5, VOCAB, SECONDS),
               key=lambda s: len(s["plan"]["output"]))
    seen = []
    while spec is not None:
        seen.append(spec)
        served = list(range(spec["max_tokens"]))
        nxt = sessions.next_turn(mix, spec, served)
        if nxt is not None:
            k = nxt["turn"]
            assert nxt["prompt"] == spec["prompt"] + served \
                + spec["plan"]["user"][k]
            assert nxt["think_s"] == spec["plan"]["think_s"][k - 1] > 0
            assert len(nxt["prompt"]) + nxt["max_tokens"] \
                <= mix["max_context"]
        spec = nxt
    assert len(seen) > 1
    # a turn that would not fit ends the session
    tight = dict(mix, max_context=len(seen[0]["prompt"])
                 + seen[0]["max_tokens"])
    assert sessions.next_turn(tight, seen[0],
                              [0] * seen[0]["max_tokens"]) is None


def test_warm_gives_each_opened_system_prompt_once(mix):
    specs = sessions.make(mix, 9, VOCAB, SECONDS)
    warm = sessions.warm(mix, specs)
    opened = {tuple(s["prompt"][:-len(s["plan"]["user"][0])])
              for s in specs}
    assert len(warm) == len(opened) == len({s["system"] for s in specs})
    assert {tuple(p) for p in warm} == opened


def test_rehearsal_reuses_each_sessions_pages(capsys, monkeypatch):
    adopted, turns, first = {}, [], []
    next_turn, make = sessions.next_turn, sessions.make

    def making(*a):
        first.extend(make(*a))
        return first
    monkeypatch.setattr(sessions, "make", making)

    def recording(params, spec, served):
        nxt = next_turn(params, spec, served)
        if nxt is not None:
            turns.append((len(spec["prompt"]), tuple(nxt["prompt"])))
        return nxt
    monkeypatch.setattr(sessions, "next_turn", recording)

    def patch(eng):
        shared = eng.scheduler._shared_prefix_pages

        def record(request, n):
            key = tuple(request.prompt)
            adopted[key] = max(adopted.get(key, 0), n)
            return shared(request, n)
        eng.scheduler._shared_prefix_pages = record

    rc = run.main(["--workload", "qwen2-0.5b.prefix", "--seed", "4100000059",
                   "--seconds", "6", "--trace", "1", "--rehearse"],
                  patch=patch)
    assert rc == 0
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True, result["checks"]
    line = next(ln for ln in err.splitlines()
                if ln.startswith("rehearsal (CPU) metrics: "))
    hit = json.loads(line.split(": ", 1)[1])["prefix_hit_rate"]
    assert hit["unit"] == "%" and 0.0 < hit["value"] <= 100.0, hit
    later = [(n, p) for n, p in turns if p in adopted]
    assert later, "no later turn was admitted"
    stride = files.config("qwen2-0.5b")["engine"]["block_pos_stride"]
    for prev_len, prompt in later:
        assert adopted[prompt] >= prev_len // stride, (prev_len, len(prompt))
    # set-up served the system prompts, so a first turn finds its own cached
    opened = [(len(s["prompt"]) - len(s["plan"]["user"][0]),
               tuple(s["prompt"])) for s in first]
    assert all(adopted[p] >= n // stride for n, p in opened
               if p in adopted), opened
    assert sum(p in adopted for _, p in opened) >= 2
