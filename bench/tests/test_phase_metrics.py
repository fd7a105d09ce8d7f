"""The per-layer metrics that read the drive loop's own counters
(``EngineStats`` phase and work counters): a CPU rehearsal of each cell
with ``--trace 1`` prints a finite number for every one of them."""

import json
import math

import pytest

import run
from harness import files

READERS = ("schedule_ms_per_step", "operand_ms_per_step",
           "logits_fetch_ms_per_step", "sample_ms_per_step",
           "publish_ms_per_step", "batch_occupancy", "prefill_pad_share")


@pytest.mark.parametrize("cell", ["qwen2-0.5b.chat", "qwen2-0.5b.prefix",
                                  "mamba2-780m.offline"])
def test_counter_metrics_read_a_number_in_the_rehearsal(cell, capsys):
    names = [m["name"] for m in files.metrics_for(cell, True)
             if m["name"].split(".")[0] in READERS]
    assert len(names) == len(READERS)
    rc = run.main(["--workload", cell, "--seed", "3900000017",
                   "--seconds", "5", "--trace", "1", "--rehearse"])
    assert rc == 0
    _, err = capsys.readouterr()
    line = next(ln for ln in err.splitlines()
                if ln.startswith("rehearsal (CPU) metrics: "))
    got = json.loads(line.split(": ", 1)[1])
    for name in names:
        v = got[name]["value"]
        assert isinstance(v, float) and math.isfinite(v), (name, v)
        if got[name]["unit"] == "%":
            assert 0.0 <= v <= 100.0, (name, v)
        else:
            assert v >= 0.0, (name, v)
