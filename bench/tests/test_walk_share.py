"""``paged_walk_share.serve`` reads the engine's paged-walk counters: the chat
rehearsal with ``--trace 1`` prints a share in (0, 100], and a program
without the counters reads nothing, so the metric drops out of its line."""

import json
import types

import run
from harness import files


def test_walk_share_reads_the_chat_rehearsal(capsys):
    rc = run.main(["--workload", "qwen2-0.5b.chat", "--seed", "3900000017",
                   "--seconds", "5", "--trace", "1", "--rehearse"])
    assert rc == 0
    _, err = capsys.readouterr()
    line = next(ln for ln in err.splitlines()
                if ln.startswith("rehearsal (CPU) metrics: "))
    got = json.loads(line.split(": ", 1)[1])["paged_walk_share.serve"]
    assert got["unit"] == "%" and 0.0 < got["value"] <= 100.0, got


def test_walk_share_without_the_counters_reads_nothing():
    read = files.reader("paged_walk_share.serve").read
    assert read(types.SimpleNamespace(stats={"launches": 3})) is None
    assert read(types.SimpleNamespace(stats={
        "kv_pages_listed": 40, "kv_pages_walked": 7})) == 17.5
