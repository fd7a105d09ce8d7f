"""Continuous-batching drive loop over the hybrid CommandQueue.

Every step is one OpenCL-style kernel enqueue: the per-bucket step executable
(``serve_step_bs{N}``, built once per bucket by ``queue.build``) consumes the
engine's state arena plus per-slot ``tokens``/``pos`` vectors and the
StateSpec-derived indirection operands — a ``(B, T)`` **block table** of
physical page ids when any layer pages KV, a ``(B,)`` **dense slot** vector
when any layer carries O(1) recurrent state — advances every occupied slot
by one position, and returns next-token logits.  ``dense``, ``moe``,
``hybrid`` and ``ssm`` families all serve through the same loop; only the
operand list differs, and it differs by spec, not by string-matching
mixers.  The host loop scatters request tokens in, gathers sampled tokens
out, and drives the request state machine; ``queue.finish()`` after each
launch is the paper's ``clFinish``.  Each step is a ``serve/step`` profiler
span tiled by its phases (``serve/schedule``, ``serve/operands``,
``serve/wait``, ``serve/fetch``, ``serve/publish``, ``serve/sample``),
whose wall times also accumulate into ``EngineStats`` (``phases.py``).

The arena is ONE device allocation shared by every bucket: it is donated
through each enqueue — across *different* bucket executables, whose cache
operand shapes are identical by construction — so a bucket change costs no
re-zeroing and slot migration is a host-side permutation of the table rows
(zero device-side KV traffic).  Pool occupancy, not bucket width, bounds
resident sequences.

Prompt ingestion is CHUNKED: while any slot still has more than one known
token to feed, the engine launches a ``prefill_bs{N}_len{L}`` executable
(L from the ``prefill_chunks`` ladder, capped by ``s_max``) that consumes up
to L tokens per slot in one enqueue — cutting prompt replay from O(prompt)
to O(prompt / L) launches, the dominant term in time-to-first-token.
Decode-phase slots ride through the same launch with ``n_valid = 1``, so
mixed prefill/decode batches remain the norm; pure-decode batches use the
cheap one-position ``serve_step_bs{N}`` executable.  As prefill fills full
prompt pages the engine publishes them (several per chunk, possibly) to the
pool's radix prefix cache, so ANY request sharing a token-block prefix —
identical prompts, ``fork()`` siblings, distinct prompts behind one system
prompt — adopts the same physical pages at admission and resumes mid-chunk.
"""

from __future__ import annotations

import dataclasses
import time
from typing import TYPE_CHECKING, Dict, Iterator, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.hybrid import CommandQueue, HybridKernel
from repro.kernels import check_kernel_backend, default_kernel_backend
from repro.models import params as pm
from repro.serve.decode import (PagedKV, make_decode_body,
                                make_prefill_chunk_body, read_expert_counts)
from repro.serve.engine.block_cache import BlockPool, block_layout
from repro.serve.engine.request import Request, RequestState, SamplingParams
from repro.serve.engine.scheduler import (ScheduledStep, Scheduler,
                                          SchedulerConfig)
from repro.serve.engine.phases import phase
from repro.serve.engine.state_store import StateStore
from repro.serve.state import layer_state_specs

if TYPE_CHECKING:                              # no import cycle at runtime:
    from repro.serve.resilience.faults import FaultInjector  # pragma: no cover
    from repro.serve.resilience.guard import ResilienceConfig  # pragma: no cover
    from repro.serve.spec.config import SpeculationConfig  # pragma: no cover


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    s_max: int = 128                      # max cache positions per sequence
    buckets: Tuple[int, ...] = (1, 2, 4, 8)
    block_pos_stride: int = 16            # positions per KV page
    n_kv_blocks: Optional[int] = None     # pool size; None = fit max batch
    mode: str = "gemv"                    # per-slot capable decode layout
    max_steps: Optional[int] = None       # drain() safety valve
    # chunked-prefill length ladder: entries above s_max are dropped, ()
    # disables chunking (token-stepped prefill, the pre-chunking behavior)
    prefill_chunks: Tuple[int, ...] = (16, 64, 256)
    # dense state slots (DenseSpec layers); None = max bucket.  Irrelevant
    # for attention-only models.
    n_dense_slots: Optional[int] = None
    # cross-request radix prefix cache (repro.serve.prefix).  False turns
    # the pool into a pure free-list allocator — no publication, matching
    # or cached-page retention — the parity baseline for the cache.
    prefix_cache: bool = True
    # kernel selection for every step executable: "jnp" (materialized-gather
    # reference paths), "pallas" (fused paged-attention + Pallas SSD scan;
    # interpret auto-selected off-TPU) or "pallas-interpret" (interpreter
    # forced — the CPU CI variant).  Default honors REPRO_KERNEL_BACKEND.
    kernel_backend: str = dataclasses.field(
        default_factory=default_kernel_backend)
    # speculative decoding (repro.serve.spec): None = off.  When set, pure
    # decode steps draft k tokens per slot and verify them in ONE
    # ``verify_bs{N}_len{k+1}`` launch; k+1 must fit s_max.
    speculation: Optional["SpeculationConfig"] = None
    # chaos / resilience (repro.serve.resilience): a seeded FaultInjector
    # makes the drive loop inject deterministic faults at named sites; a
    # ResilienceConfig bounds step retries and sets the quarantine
    # threshold.  Setting either arms the StepGuard (an injector with no
    # explicit resilience config gets the defaults).
    fault_injector: Optional["FaultInjector"] = None
    resilience: Optional["ResilienceConfig"] = None

    def __post_init__(self):
        check_kernel_backend(self.kernel_backend)
        pc = tuple(int(c) for c in self.prefill_chunks)
        bad = [c for c in pc if c < 2]
        if bad:
            raise ValueError(
                f"prefill_chunks entries must be >= 2 (an L=1 chunk is just "
                f"a slower decode step): {bad}")
        if list(pc) != sorted(set(pc)):
            raise ValueError(
                f"prefill_chunks must be strictly ascending: {pc}")
        # store what was validated (int-normalized; floats would otherwise
        # leak into shape math and kernel-cache keys)
        object.__setattr__(self, "prefill_chunks", pc)


@dataclasses.dataclass
class EngineStats:
    steps: int = 0
    prefill_launches: int = 0             # launches with a prefilling slot
    prefill_chunk_launches: int = 0       # of which used a prefill_bs{N}_len{L}
    decode_launches: int = 0
    prompt_tokens_ingested: int = 0       # prompt-position tokens fed (a
    #                                       preemption replay re-feeds them)
    tokens_generated: int = 0
    migrations: int = 0                   # host-side table permutations only
    peak_blocks_used: int = 0             # pool occupancy high-water mark
    peak_dense_slots_used: int = 0        # dense slot high-water mark
    # speculative decoding (0 everywhere when speculation is off)
    spec_launches: int = 0                # verify_bs{N}_len{L} launches
    spec_proposed_tokens: int = 0         # draft tokens fed to verification
    spec_accepted_tokens: int = 0         # of which the target accepted
    spec_rejected_tokens: int = 0         # of which were rolled back
    spec_rollbacks: int = 0               # partial-acceptance rewinds
    # resilience counters (0 everywhere without a StepGuard)
    fault_launch_failures: int = 0        # failed launch attempts (incl. final)
    fault_retries: int = 0                # of which were retried
    fault_nonfinite: int = 0              # non-finite logits rows rolled back
    fault_quarantined: int = 0            # requests finished as "error"
    fault_pool_steals: int = 0            # injected pool-pressure episodes
    fault_stalls: int = 0                 # injected step stalls
    # radix prefix cache (0 everywhere with prefix_cache=False)
    prefix_hits: int = 0                  # pages adopted at admission
    prefix_tokens_reused: int = 0         # prompt positions never prefilled
    prefix_evictions: int = 0             # cached pages recycled under pressure
    # drive-loop wall time by phase, perf_counter_ns summed over steps
    # (repro.serve.engine.phases): each is also a ``serve/<phase>`` span
    step_ns: int = 0                      # the whole of step()
    schedule_ns: int = 0                  # scheduler.schedule(), admission too
    operand_ns: int = 0                   # operand arrays, device_put, enqueue
    wait_ns: int = 0                      # blocking on the step's logits
    fetch_ns: int = 0                     # logits copy to the host
    sample_ns: int = 0                    # sampling + request advance
    publish_ns: int = 0                   # prefix-cache page / dense publication
    # work per launch
    slots_launched: int = 0               # bucket width, summed over launches
    slots_occupied: int = 0               # non-empty slots, summed over launches
    chunk_positions: int = 0              # B x L, summed over chunk launches
    chunk_positions_fed: int = 0          # positions fed by chunk launches
    # paged-attention walk per launch of a paged model (0 without pages)
    kv_pages_walked: int = 0              # ceil(kv_len / stride), occupied slots
    kv_pages_listed: int = 0              # bucket x table width
    # a share expert layer's work per launch (0 without one), read from
    # the counts the step program returns beside its logits
    moe_rows: int = 0                     # rows the held experts computed
    moe_rows_max: int = 0                 # each layer's busiest held expert's rows
    moe_positions: int = 0                # positions routed x MoE layers

    @property
    def prefix_hit_rate(self) -> float:
        """Prompt tokens served from cached pages / total prompt tokens
        offered (reused + actually ingested).  0.0 before any prompt."""
        total = self.prefix_tokens_reused + self.prompt_tokens_ingested
        if not total:
            return 0.0
        return self.prefix_tokens_reused / total

    @property
    def spec_accept_rate(self) -> float:
        """Accepted / proposed draft tokens (0.0 before any proposal)."""
        if not self.spec_proposed_tokens:
            return 0.0
        return self.spec_accepted_tokens / self.spec_proposed_tokens

    @property
    def launches(self) -> int:
        """Total step-kernel enqueues (decode + chunked/token prefill +
        verify) — the denominator of tokens-per-launch."""
        return self.decode_launches + self.prefill_launches \
            + self.spec_launches


class ServingEngine:
    """Batch-generate service over one device mesh (cf. SHARK's
    ``BatchGenerateService``, with the CommandQueue as the session)."""

    def __init__(self, cfg, mesh, plan, *, params=None,
                 engine_cfg: Optional[EngineConfig] = None, seed: int = 0):
        ec = engine_cfg or EngineConfig()
        if ec.mode != "gemv":
            raise ValueError(
                f"engine currently serves via mode='gemv' only: {ec.mode!r}")
        q = plan.grid_q
        dshards = plan.data_size * (plan.pod_size if plan.has_pod else 1)
        if dshards != 1:
            # each data shard would need its own page id space; see ROADMAP
            # (engine on data-parallel meshes)
            raise NotImplementedError(
                f"paged engine requires data_size == 1, got {dshards} shards")
        if ec.s_max % ec.block_pos_stride:
            raise ValueError(
                f"s_max={ec.s_max} must be a multiple of "
                f"block_pos_stride={ec.block_pos_stride}")
        self.cfg, self.mesh, self.plan, self.engine_cfg = cfg, mesh, plan, ec

        blocks_per_seq = ec.s_max // ec.block_pos_stride
        n_blocks = ec.n_kv_blocks or ec.buckets[-1] * blocks_per_seq
        self.paged = PagedKV(n_blocks=n_blocks,
                             block_pos_stride=ec.block_pos_stride)
        self._table_width = blocks_per_seq
        # the per-layer state contract: which layers page KV, which carry
        # dense per-slot state — every shape, operand and lifecycle rule
        # below derives from it
        self.state_specs = layer_state_specs(
            cfg, plan, stride=ec.block_pos_stride)
        # chunk ladder (validated ascending/>=2 by EngineConfig), capped by
        # s_max: oversized entries are geometry, not user error
        self._chunks = tuple(c for c in ec.prefill_chunks if c <= ec.s_max)

        # shared lowering metadata: body/specs are batch-polymorphic, only
        # the compiled executables are per-bucket
        _, _, _, specs, pctx = make_decode_body(
            cfg, mesh, plan, batch=ec.buckets[-1], s_max=ec.s_max,
            mode=ec.mode, per_slot=True, paged=self.paged,
            kernel_backend=ec.kernel_backend)
        self.specs, self.pctx = specs, pctx
        if params is None:
            params = pm.init_params(specs, seed=seed)
            pspecs = pm.param_pspecs(specs)
            params = jax.tree.map(
                lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                params, pspecs)
        self.params = params

        lead = tuple(pctx.data_axes) if len(pctx.data_axes) > 1 \
            else pctx.data_axes[0]
        self._vec_sharding = NamedSharding(mesh, P(lead))
        self._table_sharding = NamedSharding(mesh, P(lead, None))

        layout = block_layout(cfg, plan, block_pos_stride=ec.block_pos_stride,
                              mode="paged")
        self.pool = BlockPool(n_blocks, ec.block_pos_stride, layout=layout,
                              prefix_cache=ec.prefix_cache)
        # the device state arena + dense slot lifecycle live in the store;
        # ONE allocation for the engine's lifetime, donated through every
        # enqueue.  Pages are never zeroed (stale KV past a slot's position
        # is causally masked in-kernel); dense slots ARE zeroed or
        # snapshot-restored at admission — recurrent state has no mask.
        self.store = StateStore(
            mesh, self.state_specs, n_blocks=n_blocks,
            n_slots=ec.n_dense_slots or ec.buckets[-1],
            stride=ec.block_pos_stride, pool=self.pool)
        self.scheduler = Scheduler(self.pool, SchedulerConfig(ec.buckets),
                                   state=self.store)

        self.queue = CommandQueue(mesh)
        # executable cache keyed by (bucket, L): L=0 is the one-position
        # decode step, L>0 a chunked-prefill executable from the ladder
        self._kernels: Dict[Tuple[int, int], HybridKernel] = {}
        self._bucket: Optional[int] = None
        self._rngs: Dict[str, np.random.Generator] = {}
        self.stats = EngineStats()
        self._seq = 0                       # step() calls: serve/step's seq
        # pool prefix counters are monotone for the pool's lifetime; the
        # engine folds DELTAS into stats so `eng.stats = EngineStats()`
        # resets (benchmark warmup) stay correct
        self._prefix_seen = (0, 0, 0)
        self.spec = None
        if ec.speculation is not None:
            # deferred import: spec builds on the engine package, so a
            # module-level import here would cycle through its __init__
            from repro.serve.spec.decoder import SpecDecoder
            self.spec = SpecDecoder(self, ec.speculation)
        self.guard = None
        if ec.fault_injector is not None or ec.resilience is not None:
            # deferred import for the same reason as speculation
            from repro.serve.resilience.guard import (ResilienceConfig,
                                                      StepGuard)
            self.guard = StepGuard(self, ec.resilience or ResilienceConfig())

    # -- request intake ----------------------------------------------------
    #
    # Thread-safety boundary: `check_request` is a pure read over immutable
    # engine config (callable from any thread — the async service validates
    # BEFORE crossing onto the engine thread so rejections surface at the
    # caller); everything else — submit_request, step, cancel — mutates
    # scheduler/pool/arena state and must run on the single thread that
    # drives the engine (repro.serve.service serializes all of them onto
    # its engine thread via a command queue).

    def submit(self, prompt: Sequence[int],
               sampling: Optional[SamplingParams] = None,
               **request_kw) -> Request:
        """Validate and enqueue one request; extra keywords (priority,
        tenant, ttft_deadline_s) are SLO metadata for the admission-policy
        layer."""
        return self.submit_request(Request(prompt, sampling, **request_kw))

    def fork(self, parent: Request,
             sampling: Optional[SamplingParams] = None) -> Request:
        """Submit a fork of ``parent`` (same prompt, e.g. n>1 sampling).
        Once the parent's prefill has published its full prompt pages, the
        fork's block table adopts them — the prompt KV is physically shared
        in the arena, not recomputed per sibling.  Dense (SSM) state is NOT
        ref-countable: at admission the fork's slot receives a physical
        *copy* of the parent's published boundary snapshot instead, so
        hybrid forks share prompt KV pages while owning their own
        recurrent state."""
        return self.submit_request(parent.fork(sampling))

    def check_request(self, req: Request) -> None:
        """Raise ValueError when ``req`` can never be served by this
        engine.  Pure read of immutable configuration — safe off-thread."""
        ec = self.engine_cfg
        if len(req.prompt) + req.sampling.max_tokens > ec.s_max:
            raise ValueError(
                f"prompt ({len(req.prompt)}) + max_tokens "
                f"({req.sampling.max_tokens}) exceeds s_max={ec.s_max}")
        # the request must fit the pool at its FULL grown length (plus the
        # one-token lookahead the scheduler reserves), or decode would hit
        # an unpreemptable dead end mid-flight.  Page-free (dense-only)
        # sequences have O(1) footprint: nothing to check.
        worst = min(len(req.prompt) + req.sampling.max_tokens, ec.s_max)
        if self.store.needs_pages and \
                self.pool.blocks_for(worst) > self.pool.n_blocks:
            raise ValueError(
                f"sequence needs up to {self.pool.blocks_for(worst)} KV "
                f"blocks but the pool holds {self.pool.n_blocks}")

    def submit_request(self, req: Request) -> Request:
        """Engine-thread half of intake: validate + hand to the scheduler.
        A pre-stamped ``submit_t`` (the service stamps at the client's
        ``await submit()``) is preserved so queue-wait and TTFT include the
        command-queue hop; bare callers get stamped here."""
        if not req.submit_t:
            req.submit_t = time.perf_counter()  # TTFT clock starts here
        self.check_request(req)
        self.scheduler.submit(req)
        return req

    def cancel(self, request_id: str) -> bool:
        self._rngs.pop(request_id, None)
        if self.spec is not None:
            self.spec.release(request_id)
        return self.scheduler.cancel(request_id)

    # -- per-bucket executables --------------------------------------------

    def _kernel(self, bucket: int) -> HybridKernel:
        kernel = self._kernels.get((bucket, 0))
        if kernel is None:
            ec = self.engine_cfg
            body, in_specs, out_specs, _, _ = make_decode_body(
                self.cfg, self.mesh, self.plan, batch=bucket, s_max=ec.s_max,
                mode=ec.mode, per_slot=True, paged=self.paged,
                kernel_backend=ec.kernel_backend)
            kernel = HybridKernel(
                lambda grid, *args: body(*args), grid=self.pctx.grid,
                in_specs=in_specs, out_specs=out_specs,
                name=f"serve_step_bs{bucket}", donate=(1,))
            self._kernels[(bucket, 0)] = kernel
        return kernel

    def _chunk_kernel(self, bucket: int, chunk: int) -> HybridKernel:
        kernel = self._kernels.get((bucket, chunk))
        if kernel is None:
            ec = self.engine_cfg
            body, in_specs, out_specs, _, _ = make_prefill_chunk_body(
                self.cfg, self.mesh, self.plan, batch=bucket, s_max=ec.s_max,
                chunk=chunk, paged=self.paged,
                kernel_backend=ec.kernel_backend)
            kernel = HybridKernel(
                lambda grid, *args: body(*args), grid=self.pctx.grid,
                in_specs=in_specs, out_specs=out_specs,
                name=f"prefill_bs{bucket}_len{chunk}", donate=(1,))
            self._kernels[(bucket, chunk)] = kernel
        return kernel

    # -- the drive loop ----------------------------------------------------

    def _chunk_len(self, max_remaining: int) -> Optional[int]:
        """Pick this launch's prefill chunk length: the largest ladder entry
        the biggest backlog fills, else the smallest entry (covering the
        tail with ``n_valid`` padding — so a prompt of P tokens always
        ingests in <= ceil(P / min_chunk) launches, never P).  None means
        no slot is mid-prefill (or chunking is disabled): use the
        one-position decode step."""
        if max_remaining <= 1 or not self._chunks:
            return None
        for c in reversed(self._chunks):
            if c <= max_remaining:
                return c
        return self._chunks[0]

    def _fed_count(self, r: Request, chunk: int) -> int:
        """Positions slot ``r`` consumes this chunk launch.  Dense-state
        configs clamp prefill to LAND on the request's snapshot boundary
        (the last full-page boundary inside the prompt) so the dense leaves
        there are observable on device for prefix publication — at most one
        extra launch per prompt, preserving O(prompt / L) ingestion."""
        n = min(r.remaining_known, chunk)
        if self.store.has_dense:
            m0 = self.store.snapshot_boundary(r)
            if r.num_cached < m0:
                n = min(n, m0 - r.num_cached)
        return n

    def step(self) -> bool:
        """Schedule + enqueue one step kernel; returns False when idle.

        A step is ONE enqueue either way: a ``serve_step_bs{N}`` advancing
        every slot by one position, or — whenever some slot still has a
        prompt backlog — a ``prefill_bs{N}_len{L}`` advancing slot s by
        ``min(remaining[s], L)`` positions (decode slots ride along with
        one valid position).  The trailing operands derive from the
        per-layer StateSpecs: a block table when any layer pages KV, a
        dense slot-id vector when any layer carries O(1) state.

        With a :class:`~repro.serve.resilience.guard.StepGuard` armed
        (``EngineConfig.fault_injector`` / ``.resilience``), the launch +
        commit run under its retry/rollback/quarantine discipline; the
        unguarded path below is byte-identical to the pre-resilience
        engine."""
        self._seq += 1
        with phase(self.stats, "step", seq=self._seq) as span:
            try:
                return self._step(span)
            finally:
                # every prefix-cache mutation (admission adoption, eviction
                # under allocation pressure, guard pool steals) happens
                # inside a step — fold the pool's counter deltas on every
                # exit path
                self._fold_prefix_stats()

    def _step(self, span: phase) -> bool:
        if self.guard is not None:
            self.guard.pre_schedule()
        with phase(self.stats, "schedule"):
            sd = self.scheduler.schedule()
        if sd is None:
            if self.guard is not None:
                self.guard.release_stolen()  # idle: no pages held hostage
            return False
        self._note_migration(sd)
        chunk = self._chunk_len(sd.max_remaining)
        span.annotate(bucket=sd.bucket, chunk=chunk or 0,
                      occupied=sum(r is not None for r in sd.slots))
        # speculative decoding replaces the pure-decode launch when any
        # slot yields a usable draft; with no drafts this round the plain
        # serve_step launch below runs unchanged.  With a guard armed the
        # verify launch runs under the same retry/rollback/quarantine
        # discipline as every other step.
        if chunk is None and self.spec is not None:
            if self.guard is not None:
                handled = self.guard.spec_step(sd)
                if handled is not None:
                    return True
            elif self.spec.step(sd):
                return True
        if self.guard is not None:
            return self.guard.step(sd, chunk)
        rows, fed = self._launch(sd, chunk)
        self._commit(sd, rows, fed)
        self.queue.finish()         # clFinish
        return True

    def _launch(self, sd: ScheduledStep, chunk: Optional[int]):
        """Build operands and enqueue ONE step kernel for ``sd``; returns
        ``(rows, fed)`` — the materialized next-token logits rows and the
        positions each slot consumed.  Mutates NO host request state, so a
        guarded retry can simply call it again (the injector's ``launch``
        site fires before the enqueue, ``device`` after)."""
        B = sd.bucket
        inj = self.engine_cfg.fault_injector
        with phase(self.stats, "operands"):
            pos = np.zeros((B,), np.int32)
            has_pages = self.store.needs_pages
            has_dense = self.store.has_dense
            table = np.full((B, self._table_width), -1, np.int32)
            slots = np.full((B,), -1, np.int32)
            fed = [0] * B
            dev = lambda a: jax.device_put(jnp.asarray(a), self._vec_sharding)
            dev2 = lambda a: jax.device_put(jnp.asarray(a),
                                            self._table_sharding)
            if chunk is None:
                tokens = np.zeros((B,), np.int32)
                for s, r in enumerate(sd.slots):
                    if r is not None:
                        tokens[s] = r.next_token
                        pos[s] = r.num_cached
                        if has_pages:
                            table[s, :len(r.blocks.ids)] = r.blocks.ids
                        if has_dense:
                            slots[s] = r.dense_slot
                        fed[s] = 1
                ops = ([dev2(table)] if has_pages else []) \
                    + ([dev(slots)] if has_dense else [])
                if inj is not None:
                    inj.fire("launch")
                logits, self.store.arena = self.queue.enqueue(
                    self._kernel(B), self.params, self.store.arena,
                    dev(tokens), dev(pos), *ops)
            else:
                tokens = np.zeros((B, chunk), np.int32)
                n_valid = np.zeros((B,), np.int32)
                for s, r in enumerate(sd.slots):
                    if r is None:
                        continue
                    n = self._fed_count(r, chunk)
                    seq = r.seq_tokens
                    tokens[s, :n] = seq[r.num_cached:r.num_cached + n]
                    pos[s] = r.num_cached
                    n_valid[s] = n
                    if has_pages:
                        table[s, :len(r.blocks.ids)] = r.blocks.ids
                    if has_dense:
                        slots[s] = r.dense_slot
                    fed[s] = n
                ops = ([dev2(table)] if has_pages else []) \
                    + ([dev(slots)] if has_dense else [])
                if inj is not None:
                    inj.fire("launch")
                logits, self.store.arena = self.queue.enqueue(
                    self._chunk_kernel(B, chunk), self.params,
                    self.store.arena, dev2(tokens), dev(pos), dev(n_valid),
                    *ops)
        if inj is not None:
            inj.fire("device")      # the enqueue "happened"; stats below
            #                         only count steps that got this far
        st = self.stats
        if chunk is not None:
            st.prefill_chunk_launches += 1
            st.chunk_positions += B * chunk
            st.chunk_positions_fed += sum(fed)
        st.steps += 1
        self._count_slots(sd)
        self._count_pages(pos + np.asarray(fed, np.int32))
        if sd.is_prefill:
            st.prefill_launches += 1
        else:
            st.decode_launches += 1
        return self._to_host(logits), fed

    def _count_slots(self, sd: ScheduledStep) -> None:
        """Per-launch occupancy counters, shared with the verify launch."""
        st = self.stats
        st.slots_launched += sd.bucket
        st.slots_occupied += sum(r is not None for r in sd.slots)
        st.peak_blocks_used = max(st.peak_blocks_used, self.pool.n_used)
        if self.store.slot_pool is not None:
            st.peak_dense_slots_used = max(st.peak_dense_slots_used,
                                           self.store.slot_pool.n_used)

    def _count_pages(self, kv_len: np.ndarray) -> None:
        """The paged-attention walk of one launch, shared with the verify
        launch: ``kv_len`` (B,) is one past each slot's last fed position
        (0 for an empty slot), the bound the kernel walks each slot's
        table to."""
        if self.store.needs_pages:
            stride = self.engine_cfg.block_pos_stride
            self.stats.kv_pages_walked += int(np.sum(-(-kv_len // stride)))
            self.stats.kv_pages_listed += len(kv_len) * self._table_width

    def _to_host(self, logits, pos=0) -> np.ndarray:
        """The step's logits at ``pos`` (an index or slice over positions),
        cut to the vocabulary, on the host: blocking on the sliced array
        is the ``serve/wait`` phase, the copy ``serve/fetch``."""
        with phase(self.stats, "wait"):
            sl = logits[:, pos, :self.cfg.vocab_size]
            sl.block_until_ready()
        with phase(self.stats, "fetch"):
            rows = np.asarray(sl)
            moe = read_expert_counts(self.cfg, logits)
            if moe is not None:
                st = self.stats
                st.moe_rows += moe[0]
                st.moe_rows_max += moe[1]
                st.moe_positions += moe[2]
            return rows

    def _commit(self, sd: ScheduledStep, rows: np.ndarray, fed,
                skip=frozenset()) -> None:
        """Advance the request state machine with a successful launch's
        results.  Slots in ``skip`` (guard-poisoned rows) advance NOTHING —
        their pre-step snapshot was restored, so next step re-feeds the
        same positions.  Every slot's cache advance and prefix publication
        (``serve/publish``) comes before any sampling (``serve/sample``),
        so each phase is one span per step."""
        sampled = []
        with phase(self.stats, "publish"):
            for s, r in enumerate(sd.slots):
                if r is None or s in skip:
                    continue
                n = fed[s]
                # the launch fed seq_tokens[num_cached : num_cached + n];
                # its logits extend the sequence iff that range ends at the
                # last known token (the per-token samples_this_step rule,
                # chunked)
                if r.num_cached + n == len(r.seq_tokens):
                    sampled.append(s)
                # count only the fed positions inside the prompt: replayed
                # OUTPUT tokens (recompute preemption) are not prompt
                # ingestion, while re-fed prompt positions are — the kernel
                # really re-ran
                prev_cached = r.num_cached
                self.stats.prompt_tokens_ingested += max(
                    0, min(prev_cached + n, len(r.prompt)) - prev_cached)
                r.num_cached += n
                r.fault_failures = 0    # a committed step clears the
                #                         quarantine count — "repeatedly"
                #                         means consecutively
                self._publish_filled_pages(r, prev_cached, r.num_cached)
                self._maybe_publish_dense(r)
        with phase(self.stats, "sample"):
            for s in sampled:
                r = sd.slots[s]
                tok = self._sample(r, rows[s])
                r.output_tokens.append(tok)
                if len(r.output_tokens) == 1:
                    r.first_token_t = time.perf_counter()
                self.stats.tokens_generated += 1
                if r.state == RequestState.PREFILL:
                    r.transition(RequestState.DECODE)
                reason = r.finish_reason_for(tok, self.engine_cfg.s_max)
                if reason is not None:
                    self.scheduler.complete(r, reason)
                    self._rngs.pop(r.request_id, None)
                    if self.spec is not None:
                        self.spec.release(r.request_id)

    def _fold_prefix_stats(self) -> None:
        """Fold the pool's monotone prefix counters into ``stats`` as
        deltas (reset-tolerant: a freshly assigned EngineStats just resumes
        accumulating from the current pool totals)."""
        p = self.pool
        cur = (p.n_prefix_hits, p.n_prefix_tokens_reused,
               p.n_prefix_evictions)
        seen = self._prefix_seen
        self.stats.prefix_hits += cur[0] - seen[0]
        self.stats.prefix_tokens_reused += cur[1] - seen[1]
        self.stats.prefix_evictions += cur[2] - seen[2]
        self._prefix_seen = cur

    def _note_migration(self, sd: ScheduledStep) -> None:
        """Bucket/slot churn is pure table bookkeeping now — the KV pages a
        slot references are bucket-invariant, so nothing moves on device.
        We still count the events the dense engine used to pay a
        ``jnp.take`` arena copy for."""
        identity = all(m == -1 or m == s for s, m in enumerate(sd.slot_map))
        survived = any(m != -1 for m in sd.slot_map)
        if survived and (sd.bucket != self._bucket or not identity):
            self.stats.migrations += 1
        self._bucket = sd.bucket

    def _publish_filled_pages(self, r: Request, old_nc: int,
                              new_nc: int) -> None:
        """Publish every page the launch completed in (old_nc, new_nc] that
        covers prompt tokens only, so identical prompts (and forks) can
        adopt it — one chunked launch may fill several pages at once."""
        if not self.store.needs_pages:
            return
        stride = self.pool.block_pos_stride
        for t in range(old_nc // stride + 1, new_nc // stride + 1):
            end = t * stride
            if end <= len(r.prompt):
                self.pool.publish_prefix(tuple(r.prompt[:end]),
                                         r.blocks.ids[t - 1])

    def _maybe_publish_dense(self, r: Request) -> None:
        """Dense analogue of page publication: when a prefill launch lands
        exactly on the request's snapshot boundary (prefill chunks are
        clamped to guarantee it), snapshot the dense leaves there keyed by
        the consumed prompt prefix — identical prompts and ``fork()``
        siblings then *copy* that state at admission (dense state shares by
        physical copy, never by ref-count)."""
        if not self.store.has_dense:
            return
        m0 = self.store.snapshot_boundary(r)
        if 0 < m0 == r.num_cached:
            self.store.publish_dense_prefix(tuple(r.prompt[:m0]),
                                            r.dense_slot)

    def _sample(self, req: Request, row: np.ndarray) -> int:
        t = req.sampling.temperature
        if t <= 0.0:
            return int(np.argmax(row))
        rng = self._rngs.get(req.request_id)
        if rng is None:
            rng = self._rngs[req.request_id] = \
                np.random.default_rng(req.sampling.seed)
        z = row.astype(np.float64) / t
        z -= z.max()
        p = np.exp(z)
        return int(rng.choice(len(row), p=p / p.sum()))

    def drain(self) -> None:
        """Run until every submitted request reaches FINISHED."""
        steps = 0
        limit = self.engine_cfg.max_steps
        while self.scheduler.has_work:
            if not self.step():
                break
            steps += 1
            if limit is not None and steps > limit:
                raise RuntimeError(f"drain exceeded max_steps={limit}")
        if self.guard is not None:
            self.guard.release_stolen()
        self.queue.finish()

    # -- graceful drain / restore ------------------------------------------

    def drain_to(self, path: str) -> int:
        """Graceful shutdown half: checkpoint every live request's resume
        record to ``path`` (atomic JSON), then finish them all as
        ``"drained"`` — pages and dense slots return to their pools, and a
        fresh engine can :meth:`restore_from` the file to continue each
        generation token-for-token.  Returns the number checkpointed.

        A speculative round still in flight (its verify launch faulted or
        was interrupted before commit) is rolled back FIRST, so the
        checkpoint can only ever capture committed state — never a
        pre-verify draft tail."""
        from repro.serve.resilience.checkpoint import checkpoint_requests
        if self.spec is not None:
            self.spec.rollback_in_flight()
        n = checkpoint_requests(self, path)
        for r in self.scheduler.drain_all("drained"):
            self._rngs.pop(r.request_id, None)
            if self.spec is not None:
                self.spec.release(r.request_id)
        return n

    def checkpoint_to(self, path: str, *, fsync: bool = True) -> int:
        """Periodic (non-draining) checkpoint: durably write every live
        request's resume record WITHOUT finishing anything — the replica
        supervisor's incremental handoff file, taken between steps while
        generation keeps running.  Any in-flight speculative round is
        rolled back first (a no-op between committed rounds), same rule as
        :meth:`drain_to`.  Returns the number checkpointed."""
        from repro.serve.resilience.checkpoint import checkpoint_requests
        if self.spec is not None:
            self.spec.rollback_in_flight()
        return checkpoint_requests(self, path, fsync=fsync)

    def restore_from(self, path: str) -> list:
        """Resubmit a drain checkpoint's requests into this engine (rng
        states included); each resumes mid-generation via prompt+output
        replay.  Returns the restored requests in re-admission order."""
        from repro.serve.resilience.checkpoint import restore_requests
        return restore_requests(self, path)

    def stream(self, prompt: Sequence[int],
               sampling: Optional[SamplingParams] = None) -> Iterator[int]:
        """Streaming facade: submit one request NOW (the TTFT clock starts
        here, and other drivers can advance it before the first ``next()``)
        and return a generator yielding its tokens as they are sampled.
        Each ``next()`` drives the WHOLE engine forward (concurrent
        requests keep advancing), so interleaving streams with
        ``submit()``/``step()`` is legal; the yielded sequence is exactly
        what :func:`repro.serve.engine.api.generate` would return for the
        same prompt/params.  Abandoning the generator early (close /
        GeneratorExit) cancels the request, releasing its KV blocks instead
        of leaving it running headless."""
        req = self.submit(prompt, sampling)

        def _gen():
            emitted = 0
            try:
                while not req.is_finished:
                    if not self.step():
                        break
                    while emitted < len(req.output_tokens):
                        yield req.output_tokens[emitted]
                        emitted += 1
                while emitted < len(req.output_tokens):
                    yield req.output_tokens[emitted]
                    emitted += 1
            finally:
                if not req.is_finished:
                    self.cancel(req.request_id)

        return _gen()

    # -- observability -----------------------------------------------------

    @property
    def _arena(self):
        """The device state arena (owned by the StateStore)."""
        return self.store.arena

    @property
    def prefill_chunk_ladder(self) -> Tuple[int, ...]:
        """Effective chunked-prefill lengths (config ladder capped by s_max,
        ascending; empty = token-stepped prefill)."""
        return self._chunks

    def kernel_events(self):
        return {name: ev for name, ev in self.queue.events.items()
                if name.startswith(("serve_step_bs", "prefill_bs",
                                    "verify_bs"))}

    def throughput_tok_s(self) -> float:
        """Generated tokens over the summed wall time of ``step()``
        (``EngineStats.step_ns``); 0.0 before any step."""
        if not self.stats.step_ns:
            return 0.0
        return self.stats.tokens_generated * 1e9 / self.stats.step_ns

    def peak_kv_bytes(self) -> int:
        """Peak resident state bytes: pool occupancy x per-page footprint
        plus dense slot occupancy x per-slot footprint (both priced by the
        StateSpec list; either term is zero when that state kind is
        absent)."""
        layout = self.pool.layout
        per = layout.bytes_per_block if layout is not None else 0
        dense = self.stats.peak_dense_slots_used * self.store.dense_slot_bytes
        return self.stats.peak_blocks_used * per + dense
