"""The ``granite-4.0-h-small.offline`` cell on the CPU: its rehearsal (the
program's tiny sibling of the model with interpreted kernels) reads
correct, and reads not correct with its timed path broken by each fault of
``test_faults.py`` or with the float8 reference's first choices scored in
place of the served tokens; the readers of its two per-layer metrics give
pinned values on a fixed launch log, a fixed reduced trace and fixed
counters, and nothing on a program without the counters."""

import json
import types

import pytest

import run
from harness import files, flops, program
from test_faults import _half, _state, _token

CELL = "granite-4.0-h-small.offline"


@pytest.mark.parametrize("fault,correct", [
    (None, True), (_token, False), (_state, False), (_half, False)],
    ids=["sound", "token", "state", "half"])
def test_the_rehearsal_catches_each_fault(fault, correct, capsys):
    rc = run.main(["--workload", CELL, "--seed", "4100000017",
                   "--seconds", "5", "--trace", "0", "--rehearse"],
                  patch=fault)
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is correct, result["checks"]
    assert result["attempted"] > 0


def test_the_float8_control_fails_the_rehearsal_limit(capsys):
    rc = run.main(["--workload", CELL, "--seed", "4100000023",
                   "--seconds", "5", "--trace", "0", "--rehearse",
                   "--control"])
    assert rc == 0
    out, err = capsys.readouterr()
    gap = json.loads(out.strip().splitlines()[-1])["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    info = next(json.loads(line) for line in err.splitlines()
                if line.startswith('{"setup_s"'))
    assert info["served_logit_gap"] <= gap["limit"]


def _sizes():
    conf = files.config("granite-4.0-h-small")
    return program.sizes(program.model_config(conf, False), conf)


STATS = {"moe_rows": 12500, "moe_rows_max": 1800, "moe_positions": 10000}
# two traced launches: a decode step of 64 slots, then a prefill chunk of
# 256 whose slots feed 256, 100 and 1 positions
LAUNCHES = [
    {"seq": 0, "kind": "decode", "bucket": 64, "chunk": 1,
     "slots": [(300 + i, 1, True) for i in range(64)]},
    {"seq": 1, "kind": "prefill", "bucket": 64, "chunk": 256,
     "slots": [(0, 256, False), (512, 100, True), (900, 1, True)]},
]
RED = {"steps": [{"start": 0, "end": 100_000_000, "seq": 0},
                 {"start": 200_000_000, "end": 400_000_000, "seq": 1},
                 {"start": 500_000_000, "end": 600_000_000, "seq": None}],
       "ops": [("gmm", 1_000_000, 4_000_000), ("gmm", 5_000_000, 6_000_000),
               ("fusion", 6_000_000, 9_000_000),
               ("gmm", 210_000_000, 260_000_000),
               ("gmm", 510_000_000, 520_000_000)]}


def _ctx(stats):
    return types.SimpleNamespace(stats=stats, s=_sizes(), red=RED,
                                 launches=LAUNCHES,
                                 peak=flops.peak("TPU v5 lite"))


def test_the_expert_roofline_reads_a_pinned_value():
    got = files.reader("moe_expert_roofline").read(_ctx(STATS))
    # by hand: 1.25 rows a position a layer over 10 layers; a row is
    # 6 x 4096 x 768 FLOPs; each launch reads 9 experts x 3 x 4096 x 768
    # bf16 weights a layer, and 2 x 4096 bf16 a row
    time = 0.0
    for fed in (64, 357):
        rows = fed * 1.25 * 10
        time += max(rows * 6 * 4096 * 768 / 197e12,
                    (10 * 9 * 3 * 4096 * 768 * 2 + rows * 2 * 4096 * 2)
                    / 819e9)
    assert got == pytest.approx(100 * time / 0.054, rel=1e-12)
    assert got == 7.876830461719351


def test_the_load_imbalance_reads_a_pinned_value():
    assert files.reader("expert_load_imbalance").read(_ctx(STATS)) \
        == pytest.approx(29.6, rel=1e-12)
    even = dict(STATS, moe_rows_max=12500 // 9)
    assert abs(files.reader("expert_load_imbalance").read(_ctx(even))) < 0.1


@pytest.mark.parametrize("name", ["moe_expert_roofline",
                                  "expert_load_imbalance"])
def test_a_program_without_the_counters_reads_nothing(name):
    assert files.reader(name).read(_ctx({"launches": 3})) is None
