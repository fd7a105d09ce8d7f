"""The control at a size a test run can hold: a whole CPU rehearsal with
the float8 reference's first choices scored in place of the served tokens
(``run.py --control``) must come out not correct, by the same checks that
pass the served tokens."""

import json

import pytest

import run


@pytest.mark.parametrize("cell", ["qwen2-0.5b.chat", "mamba2-780m.offline"])
def test_float8_control_fails_the_rehearsal_limit(cell, capsys):
    rc = run.main(["--workload", cell, "--seed", "4100000023",
                   "--seconds", "5", "--trace", "0", "--rehearse",
                   "--control"])
    assert rc == 0
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    gap = result["checks"]["logit_gap"]
    assert result["correct"] is False, result["checks"]
    assert gap["value"] > gap["limit"]
    assert result["checks"]["bad_requests"]["value"] == 0
    info = next(json.loads(line) for line in err.splitlines()
                if line.startswith('{"setup_s"'))
    assert info["served_logit_gap"] <= gap["limit"]
