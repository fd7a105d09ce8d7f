"""OpenCL-style host-offload API wrapping nested SHMEM device programs.

The paper's execution model, transliterated to JAX:

  OpenCL host code            -> Python on the controller host
  clCreateCommandQueue        -> CommandQueue(mesh)
  clBuildProgram / kernel     -> HybridKernel(fn): shard_map(fn) over the mesh,
                                 with the SHMEM grid injected as first arg
  clEnqueueNDRangeKernel      -> queue.enqueue(kernel, *args) -> jit dispatch
  clFinish                    -> queue.finish() (block_until_ready)
  cl_mem global buffers       -> device arrays with NamedShardings

Each enqueue is one "OpenCL kernel launch" containing a complete OpenSHMEM
parallel job (the ShmemGrid), scoped to that launch — matching the paper's
rule that SHMEM state does not persist across kernel invocations.  The queue
records per-kernel lowering stats (FLOPs, bytes, collectives) so offload
traffic is observable, mirroring OpenCL event profiling.
"""

from __future__ import annotations

import dataclasses
import re
import time
from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence

import jax
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.shmem import ShmemGrid


@dataclasses.dataclass
class KernelEvent:
    """Profiling record for one kernel (cl_event analogue): its lowering
    cost stats, launch count and cumulative build time.  Host time around
    launches is the drive loop's to measure (``EngineStats`` phase
    counters), not the queue's.
    """

    name: str
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collective_bytes: float = 0.0
    launches: int = 0
    build_time_s: float = 0.0


class HybridKernel:
    """A device kernel: an OpenSHMEM program nested in an offloadable launch.

    ``fn(grid, *args)`` is written in device-level style: it sees per-PE local
    blocks and communicates via the :class:`ShmemGrid`.  ``in_specs`` /
    ``out_specs`` are the cl_mem layouts of its operands.
    """

    def __init__(self, fn: Callable, *, grid: ShmemGrid, in_specs, out_specs,
                 name: Optional[str] = None, donate: Sequence[int] = ()):
        self.fn = fn
        self.grid = grid
        self.in_specs = in_specs
        self.out_specs = out_specs
        self.name = name or getattr(fn, "__name__", "kernel")
        self.donate = tuple(donate)

    def bind(self, mesh: Mesh) -> Callable:
        """The jitted launch, named after the kernel so the compiled module
        reads ``jit_<name>`` in a profiler trace."""
        body = partial(self.fn, self.grid)
        mapped = jax.shard_map(body, mesh=mesh, in_specs=self.in_specs,
                               out_specs=self.out_specs, check_vma=False)

        def program(*args):
            return mapped(*args)

        program.__name__ = program.__qualname__ = self.name
        return jax.jit(program, donate_argnums=self.donate)


class CommandQueue:
    """In-order command queue for one device mesh (cl_command_queue analogue)."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.events: Dict[str, KernelEvent] = {}
        self._compiled: Dict[str, Any] = {}
        self._pending = []
        self.max_depth = 0              # high-water mark of in-flight enqueues

    @property
    def depth(self) -> int:
        """Number of enqueued-but-not-drained dispatches (queue occupancy)."""
        return len(self._pending)

    @property
    def n_executables(self) -> int:
        """Distinct compiled executables held by this queue."""
        return len(self._compiled)

    def build(self, kernel: HybridKernel, *example_args) -> Any:
        """clBuildProgram: lower + compile for this mesh, record cost stats.

        ``build_time_s`` accumulates across rebuilds, but per-launch cost
        stats (flops / bytes / collective bytes) are stamped on the FIRST
        build only: a rebuild of the same kernel name must not clobber the
        record callers may already be aggregating against.
        """
        t0 = time.perf_counter()
        with TraceAnnotation("serve/build", name=kernel.name):
            compiled = kernel.bind(self.mesh).lower(*example_args).compile()
        ev = self.events.setdefault(kernel.name, KernelEvent(kernel.name))
        ev.build_time_s += time.perf_counter() - t0
        if kernel.name not in self._compiled:
            try:
                cost = compiled.cost_analysis()
                cost = cost[0] if isinstance(cost, (list, tuple)) else cost
                ev.flops = float(cost.get("flops", 0.0))
                ev.bytes_accessed = float(cost.get("bytes accessed", 0.0))
            except Exception:  # cost analysis is best-effort on some backends
                pass
            # optimized HLO (dash-form op names); stablehlo uses underscores
            ev.collective_bytes = collective_bytes_from_hlo(compiled.as_text())
        self._compiled[kernel.name] = compiled
        return compiled

    def enqueue(self, kernel: HybridKernel, *args):
        """clEnqueueNDRangeKernel: async dispatch; returns device futures.

        Donated operands (``kernel.donate``) may flow between enqueues of
        DIFFERENT kernels: an output of one executable is a legal donated
        input to the next as long as shape/sharding match — the serving
        engine threads its bucket-invariant paged KV arena through every
        ``serve_step_bs{N}`` this way, so the arena is one allocation for
        the queue's whole lifetime.
        """
        if kernel.name not in self._compiled:
            self.build(kernel, *args)
        out = self._compiled[kernel.name](*args)
        self.events[kernel.name].launches += 1
        self._pending.append(out)
        self.max_depth = max(self.max_depth, len(self._pending))
        return out

    def finish(self):
        """clFinish: block until all enqueued work completes."""
        jax.block_until_ready(self._pending)
        self._pending.clear()


def _shape_bytes(shape_str: str) -> float:
    """Bytes of one HLO shape string like 'bf16[4,128,256]{2,1,0}'."""
    m = re.match(r"(\w+)\[([\d,]*)\]", shape_str)
    if not m:
        return 0.0
    dtype, dims = m.groups()
    sizes = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
             "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8, "c64": 8, "c128": 16}
    nbytes = sizes.get(dtype, 4)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return float(n * nbytes)


def collective_bytes_from_hlo(hlo_text: str) -> float:
    """Sum output-shape bytes over every collective op in an HLO module.

    Used for the roofline collective term: cost_analysis() does not report
    inter-device traffic, so we parse the stable-HLO/HLO text.  Counts each
    collective's result size (per-participant payload).
    """
    total = 0.0
    for line in hlo_text.splitlines():
        line = line.strip()
        # Match lines like: '%ag = bf16[8,128]{1,0} all-gather(...)' or
        # 'x = bf16[...] collective-permute(...)'
        m = re.search(
            r"=\s+((?:\w+\[[^\]]*\](?:\{[^}]*\})?|\([^)]*\)))\s+"
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
            r"(?:-start)?\b", line)
        if not m:
            continue
        shape_str = m.group(1)
        if shape_str.startswith("("):  # tuple shape: sum elements
            for part in re.findall(r"\w+\[[^\]]*\]", shape_str):
                total += _shape_bytes(part)
        else:
            total += _shape_bytes(shape_str)
    return total
