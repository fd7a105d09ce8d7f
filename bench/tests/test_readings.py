"""The readings of today's cells do not move when the harness counts by
the layer pattern: on a fixed launch log laid over the trace recorded on
the chip (``data/chat.xplane.pb.gz``), ``step_mfu`` and
``paged_attn_roofline`` read exactly what they read before, and the FLOP
and byte totals are the same to the FLOP.  The constants were read with
the harness that multiplied by ``n_layers`` and branched on ``family``."""

import gzip
import os
import shutil
import types

import pytest

from harness import files, flops, program, trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "chat.xplane.pb.gz")

BEFORE = {
    "qwen2-0.5b": {"step_mfu": 3.5698512301473664,
                   "paged_attn_roofline": 2.796986673763928,
                   "launch_flops": 3070381117440.0,
                   "paged_need": (8495603200.0, 47406592.0)},
    "mamba2-780m": {"step_mfu": 6.8806763778449485,
                    "launch_flops": 5917977378816.0},
}


def launch_log():
    """Ten launches for the ten traced steps: eight prefill chunks of mixed
    positions (one slot not sampling), then two decode steps."""
    out = []
    for i in range(10):
        if i < 8:
            slots = [(0, 256, False), (256 * i, 200, True),
                     (1024 + 64 * i, 37, True), (3000 - 16 * i, 5, i % 2 == 0)]
            out.append({"seq": i, "kind": "prefill", "bucket": 8,
                        "chunk": 256, "slots": slots})
        else:
            slots = [(p + i, 1, True) for p in (100, 777, 2048, 3000, 4000)]
            out.append({"seq": i, "kind": "decode", "bucket": 8, "chunk": 1,
                        "slots": slots})
    return out


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    raw = tmp_path_factory.mktemp("trace") / "chat.xplane.pb"
    with gzip.open(FIXTURE, "rb") as src, open(raw, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.reduce(trace.load(str(raw)))


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_readings_are_unchanged_on_a_fixed_launch_log(name, red):
    conf = files.config(name)
    s = program.sizes(program.model_config(conf, False), conf)
    ref = files.module("reference", conf["reference"])
    log = launch_log()
    ctx = types.SimpleNamespace(red=red, launches=log, s=s, ref=ref,
                                peak=flops.peak("TPU v5 lite"))
    want = BEFORE[name]
    assert sum(flops.launch_model_flops(s, rec["slots"], ref)
               for rec in log) == want["launch_flops"]
    assert files.reader("step_mfu").read(ctx) == want["step_mfu"]
    if "paged_need" in want:
        need = [flops.paged_attention_need(s, rec["slots"]) for rec in log]
        assert (sum(f for f, _ in need), sum(b for _, b in need)) \
            == want["paged_need"]
        assert files.reader("paged_attn_roofline").read(ctx) \
            == want["paged_attn_roofline"]
