"""Kernel microbenchmarks (host XLA:CPU wall time + structural bytes).

Interpret-mode Pallas timing is Python-loop time, not TPU time — so the
timed entries here are the pure-jnp production paths (chunked attention,
SSD scan) vs their quadratic/sequential references, which DO run real
XLA:CPU code.  The Pallas kernels are covered by structural metrics (VMEM
working set, HBM->VMEM traffic per block) that transfer to TPU directly.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

import numpy as np

from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.paged_attention import paged_attention
from repro.kernels.paged_attention.kernel import walk_shape
from repro.kernels.ssd_scan import ssd_ref, ssd_scan
from repro.models.attention import chunked_attention


def _bench(f, *args, iters=5):
    out = f(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run(report):
    # chunked (flash) attention vs materialized reference, growing S
    for S in (512, 2048):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (1, 8, S, 64), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, S, 64), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, S, 64), jnp.float32)
        f_chunk = jax.jit(lambda q, k, v: chunked_attention(
            q, k, v, q_offset=0, block_kv=512))
        f_ref = jax.jit(lambda q, k, v: attention_ref(q, k, v))
        report(f"attn_chunked_S{S}_us", round(_bench(f_chunk, q, k, v), 1),
               f"score_mem=O(S*{min(512, S)})")
        report(f"attn_ref_S{S}_us", round(_bench(f_ref, q, k, v), 1),
               f"score_mem=O(S^2)={4*S*S*8/1e6:.0f}MB")

    # SSD chunked scan vs sequential recurrence
    B, S, H, P, G, N = 1, 2048, 8, 32, 1, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    f_chunk = jax.jit(lambda *a: ssd_scan(*a, chunk=128)[0])
    f_seq = jax.jit(lambda *a: ssd_ref(*a)[0])
    report("ssd_chunked_S2048_us", round(_bench(f_chunk, x, dt, A, Bm, Cm), 1),
           "parallel chunks + assoc state scan")
    report("ssd_sequential_S2048_us", round(_bench(f_seq, x, dt, A, Bm, Cm), 1),
           "step-by-step recurrence")

    # paged decode: materialized gather (the jnp serving path) vs the fused
    # kernel's in-place page reads.  The timed entry is the real XLA:CPU
    # gather+attend path; the fused kernel is priced structurally (bytes of
    # gathered K/V copy it never materializes — per layer, per launch).
    B, T, stride, kvh, hd, Hq = 8, 32, 16, 2, 64, 8
    n_loc = B * T
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    kc = jax.random.normal(ks[0], (n_loc, stride, kvh, hd), jnp.float32)
    vc = jax.random.normal(ks[1], (n_loc, stride, kvh, hd), jnp.float32)
    q = jax.random.normal(ks[2], (B, Hq, 1, hd), jnp.float32)
    table = np.arange(B * T, dtype=np.int32)
    np.random.default_rng(0).shuffle(table)
    table = jnp.asarray(table.reshape(B, T))
    q_pos = jnp.full((B, 1), T * stride - 1, jnp.int32)
    kv_len = q_pos[:, 0] + 1
    f_gather = jax.jit(lambda *a: paged_attention(
        *a, stride=stride, row=0, qrows=1, backend="jnp"))
    report("paged_decode_gather_us",
           round(_bench(f_gather, q, kc, vc, table, q_pos, kv_len), 1),
           f"jnp: materializes (B,{T * stride},{kvh},{hd}) K/V per call")
    copy_bytes = 2 * B * T * stride * kvh * hd * 4      # K and V, fp32
    report("paged_decode_gather_copy_KB", round(copy_bytes / 1024, 1),
           "gathered-copy traffic the fused kernel eliminates per launch")
    _, pages = walk_shape(1, stride, T)
    page_kb = 2 * 2 * pages * stride * kvh * hd * 4 / 1024
    report("paged_fused_vmem_page_KB", round(page_kb, 1),
           f"fused kernel VMEM working set: two blocks of {pages} (stride="
           f"{stride}) page pairs + running (m,l,acc)")

    # Pallas cannon_mm structural numbers (transfer to TPU directly)
    bm = bn = bk = 256
    vmem = (bm * bk + bk * bn) * 2 + bm * bn * 4
    report("cannon_mm_vmem_block_KB", round(vmem / 1024, 1),
           f"blocks=({bm},{bn},{bk}) bf16+fp32acc, fits 16MB VMEM")
    M = K = N = 4096
    naive = (M * K + K * N) * (N // bn) * 2   # re-read per output tile
    blocked = (M * K * (N // bn) + K * N * (M // bm)) * 2
    ideal = (M * K + K * N) * 2
    report("cannon_mm_hbm_reuse_x", round(naive / blocked, 2),
           "HBM traffic naive/blocked at 4096^3")
