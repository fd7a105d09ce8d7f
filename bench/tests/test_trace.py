"""The trace reduction on a small trace recorded on the chip
(``run.py --workload qwen2-0.5b.chat --seconds 2 --trace 1 --keep-trace``
on one TPU v5e, gzipped), read on the CPU."""

import gzip
import os
import shutil

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FIXTURE = os.path.join(DATA, "chat.xplane.pb.gz")


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    raw = tmp_path_factory.mktemp("trace") / "chat.xplane.pb"
    with gzip.open(FIXTURE, "rb") as src, open(raw, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.reduce(trace.load(str(raw)))


def test_busy_and_idle_add_up_to_the_window(red):
    assert 0 < red["busy_ns"] <= red["window_ns"]
    idle = sum(red["idle_ns"].values())
    assert idle + red["busy_ns"] == pytest.approx(red["window_ns"], rel=1e-6)


def test_steps_carry_their_launch_and_device_time(red):
    steps = [s for s in red["steps"] if s["seq"] is not None]
    assert steps and {s["kind"] for s in steps} <= {"decode", "prefill"}
    for s in steps:
        assert 0 <= s["busy"] <= s["end"] - s["start"]
    seqs = [s["seq"] for s in steps]
    assert seqs == sorted(seqs)


def test_device_time_is_charged_to_host_spans(red):
    # the device waits on the host: some of its idle time falls inside
    # the benchmark's spans around the program's calls
    assert any(k.startswith("bench/") for k in red["idle_ns"])


def test_paged_kernel_is_found_by_name_inside_busy_time(red):
    steps = [s for s in red["steps"] if s["seq"] is not None]
    t = trace.kernel_ns(red, r"^paged_attention$", steps)
    assert 0 < t <= sum(s["busy"] for s in steps)
    assert red["ops_ns"]["paged_attention"] >= t


def test_breakdown_keeps_ten_of_each(red):
    b = trace.breakdown(red)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
