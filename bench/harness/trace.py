"""Reduction of one profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

* device busy: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:n`` plane), averaged
  over the devices;
* the host's ``bench/step`` spans (``harness.program.LaunchLog``), each with
  the device-busy time inside it and the kind of its launch;
* device self time by operation name (the HLO instruction's name without
  its ``.N`` suffix, so ``%paged_attention.8 = ...`` reads
  ``paged_attention``; a ``while`` loop's time excludes the operations
  inside it);
* idle gaps of the device, each charged to the innermost ``bench/`` host
  span that was open at its middle (``idle`` when none was).

Host spans and device operations share the profiler's clock.
"""

from __future__ import annotations

import bisect
import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+")
OPS_LINE = "XLA Ops"


def _union(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(union, starts, s, e):
    """Length of [s, e) covered by the sorted disjoint intervals."""
    i = max(0, bisect.bisect_right(starts, s) - 1)
    tot = 0
    while i < len(union) and union[i][0] < e:
        a, b = union[i]
        tot += max(0, min(b, e) - max(a, s))
        i += 1
    return tot


def _op_name(text: str) -> str:
    return re.sub(r"\.\d+$", "", text.split(" = ", 1)[0].lstrip("%"))


def _self_times(ops):
    """Per operation name, duration less that of the operations nested in
    it (the line nests a loop's body inside the loop)."""
    out = collections.Counter()
    stack = []                       # [name, end, self]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            n, _, own = stack.pop()
            out[n] += own
        if stack:
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    for n, _, own in stack:
        out[n] += own
    return out


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def reduce(pd) -> dict:
    """Everything in nanoseconds on the profiler's clock."""
    devices = []
    host = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(_op_name(e.name), e.start_ns,
                             e.start_ns + e.duration_ns) for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench/"):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns,
                                     _stat(e, "seq")))
    if not devices or not any(devices):
        raise ValueError("the trace holds no device operations; planes: "
                         + ", ".join(p.name for p in pd.planes))
    ops0 = devices[0]
    union = _union([(s, e) for _, s, e in ops0])
    starts = [u[0] for u in union]
    all_t = [t for ops in devices for _, s, e in ops for t in (s, e)] \
        + [t for _, s, e, _ in host for t in (s, e)]
    t_lo, t_hi = min(all_t), max(all_t)
    busy = sum(
        sum(e - s for s, e in _union([(s, e) for _, s, e in ops]))
        for ops in devices) / len(devices)

    by_op = _self_times(ops0)

    spans = sorted(host, key=lambda h: h[1])
    steps = []
    launches = [h for h in spans if h[0].startswith("bench/launch/")]
    li = 0
    for name, s, e, _ in spans:
        if name != "bench/step":
            continue
        while li < len(launches) and launches[li][1] < s:
            li += 1
        kind, seq = None, None
        if li < len(launches) and launches[li][2] <= e:
            kind = launches[li][0].rsplit("/", 1)[1]
            seq = launches[li][3]
        steps.append({"start": s, "end": e, "kind": kind, "seq": seq,
                      "busy": _overlap(union, starts, s, e)})

    # spans nest one level: bench/step holds the others, which never
    # overlap one another; a gap goes to the innermost span open at its
    # middle
    inner = [h for h in spans if h[0] != "bench/step"]
    outer = [h for h in spans if h[0] == "bench/step"]
    inner_s = [h[1] for h in inner]
    outer_s = [h[1] for h in outer]

    def who(t):
        for lst, st in ((inner, inner_s), (outer, outer_s)):
            i = bisect.bisect_right(st, t) - 1
            if i >= 0 and t < lst[i][2]:
                return lst[i][0]
        return "idle"

    gaps = collections.Counter()
    prev = t_lo
    for a, b in union + [[t_hi, t_hi]]:
        if a > prev:
            gaps[who((prev + a) / 2)] += a - prev
        prev = max(prev, b)
    return {"window_ns": t_hi - t_lo, "busy_ns": busy,
            "ops_ns": dict(by_op), "steps": steps,
            "idle_ns": dict(gaps), "ops": ops0}


def kernel_ns(red: dict, pattern: str, steps) -> float:
    """Device time of the operations whose name matches ``pattern`` inside
    the given host step spans."""
    rx = re.compile(pattern)
    iv = sorted((st["start"], st["end"]) for st in steps)
    starts = [a for a, _ in iv]
    tot = 0
    for name, s, e in red["ops"]:
        if not rx.search(name):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= iv[i][1]:
            tot += e - s
    return float(tot)


def breakdown(red: dict) -> dict:
    top = sorted(red["ops_ns"].items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(red["idle_ns"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v / 1e9] for k, v in top],
            "idle_gaps": [[k, v / 1e9] for k, v in idle]}
