"""The general generator gives every seed the same work in another order."""

from harness import files
from traffic import requests


def _sizes(reqs):
    return (sorted(len(r["prompt"]) for r in reqs),
            sorted(r["max_tokens"] for r in reqs))


def test_same_work_for_every_seed():
    mix = files.traffic("chat")
    a = requests.make(mix, 1, 1000, 40.0)
    b = requests.make(mix, 2 ** 31 + 7, 1000, 40.0)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 40)
    assert _sizes(a) == _sizes(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    # the mix fixes the order: every seed meets the same work in sequence
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert max(r["due"] for r in a) < 40.0
    assert sum(r["temperature"] == 0.0 for r in a) == \
        sum(r["temperature"] == 0.0 for r in b) > 0


def test_same_seed_same_requests():
    mix = files.traffic("offline")
    assert requests.make(mix, 5, 300, 30.0) == requests.make(mix, 5, 300,
                                                             30.0)


def test_lengths_follow_the_mix():
    mix = files.traffic("chat")
    reqs = requests.make(mix, 3, 1000, 40.0)
    p = sorted(len(r["prompt"]) for r in reqs)
    assert mix["prompt"]["min"] <= p[0] and p[-1] <= mix["prompt"]["max"]
    assert abs(p[len(p) // 2] - mix["prompt"]["median"]) \
        < 0.2 * mix["prompt"]["median"]


def test_backlog_blocks_hold_the_same_lengths_and_the_shortest_is_greedy():
    mix = files.traffic("offline")
    block = mix["block"]
    for seed in (1, 99, 2 ** 31 + 3):
        reqs = requests.make(mix, seed, 300, 51.0)
        assert len(reqs) == mix["requests"]
        for start in range(0, len(reqs), block):
            blk = reqs[start:start + block]
            first = requests.make(mix, 1, 300, 51.0)[:len(blk)]
            assert _sizes(blk) == _sizes(first)
            shortest = min(blk, key=lambda r: r["max_tokens"])
            assert shortest["temperature"] == 0.0
            greedy = sum(r["temperature"] == 0.0 for r in blk)
            assert greedy == -(-len(blk) // mix["greedy_every"])
