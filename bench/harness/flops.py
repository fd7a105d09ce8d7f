"""Peaks of the chip, and the operations and bytes that the served work
needs, computed from shapes and real lengths.

Nothing here reads the program's own cost analysis: a kernel that does
work nobody needs (padding, pages past a slot's length) does not raise its
count, so removing that work reads as a gain.  ``s`` is a configuration's
``model`` block (``bench/configs/<name>.json``).
"""

from __future__ import annotations

BF16 = 2
F32 = 4

# Keyed by ``jax.Device.device_kind``.  Source: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM per chip).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak table entry for device kind "
                       f"{device_kind!r}; add its published peaks")
    return PEAKS[device_kind]


# -- whole model -------------------------------------------------------------

def dense_layer_params(s) -> int:
    D, F, hd = s["d_model"], s["d_ff"], s["head_dim"]
    nq, nk = s["n_heads"] * hd, s["n_kv_heads"] * hd
    return D * nq + 2 * D * nk + nq * D + 3 * D * F


def ssm_layer_params(s) -> int:
    D, di, H = s["d_model"], s["d_inner"], s["ssm_heads"]
    GN = s["ssm_groups"] * s["ssm_state"]
    return D * (2 * di + 2 * GN + H) + di * D


def body_flops_per_position(s) -> float:
    """Matmul FLOPs of one position through every layer (2 per weight),
    plus the SSM recurrence (state update and read-out) where there is
    one; attention over the context is counted apart."""
    L = s["n_layers"]
    if s["family"] == "ssm":
        H, P, N = s["ssm_heads"], s["ssm_headdim"], s["ssm_state"]
        conv = 2 * s["conv_kernel"] * (s["d_inner"]
                                       + 2 * s["ssm_groups"] * N)
        return L * (2 * ssm_layer_params(s) + conv + 4 * H * N * P)
    return L * 2 * dense_layer_params(s)


def head_flops(s) -> float:
    return 2.0 * s["d_model"] * s["vocab_size"]


def attention_flops(s, pos: int, n: int) -> float:
    """QK and PV FLOPs, all layers, of ``n`` positions fed from ``pos``:
    position ``pos + j`` attends to ``pos + j + 1`` keys."""
    if s["family"] == "ssm":
        return 0.0
    keys = n * pos + n * (n + 1) // 2
    return s["n_layers"] * 4.0 * s["n_heads"] * s["head_dim"] * keys


def launch_model_flops(s, slots) -> float:
    """Model FLOPs that one launch needs: ``slots`` is a list of
    ``(pos, fed, samples)`` for the occupied slots."""
    total = 0.0
    for pos, fed, samples in slots:
        total += fed * body_flops_per_position(s) \
            + attention_flops(s, pos, fed) + (head_flops(s) if samples else 0)
    return total


# -- kernels -----------------------------------------------------------------

def paged_attention_need(s, slots):
    """(FLOPs, bytes) of ONE layer's paged-attention call in a launch: each
    slot reads the K and V of its context up to its last fed position once,
    reads its queries and writes its outputs."""
    hd, nh, nkv = s["head_dim"], s["n_heads"], s["n_kv_heads"]
    flops = nbytes = 0.0
    for pos, fed, _ in slots:
        keys = fed * pos + fed * (fed + 1) // 2
        flops += 4.0 * nh * hd * keys
        nbytes += (pos + fed) * 2 * nkv * hd * BF16 \
            + 2 * fed * nh * hd * BF16
    return flops, nbytes


def ssd_need(s, slots):
    """((FLOPs, bytes) of the chunk kernel, (FLOPs, bytes) of the apply
    kernel) for ONE layer of a prefill-chunk launch: only the fed
    positions of each slot count, not the chunk's padding."""
    H, P, N = s["ssm_heads"], s["ssm_headdim"], s["ssm_state"]
    G = s["ssm_groups"]
    cf = cb = af = ab = 0.0
    for _, n, _ in slots:
        tri = n * (n + 1) // 2
        cf += H * (2.0 * tri * N + 2.0 * tri * P + 2.0 * n * N * P)
        cb += n * H * P * BF16 + n * H * F32 + 2 * n * G * N * BF16 \
            + n * H * P * BF16 + H * N * P * F32 + n * H * F32
        af += H * 2.0 * n * N * P
        ab += 2 * n * H * P * BF16 + n * G * N * BF16 + n * H * F32 \
            + H * N * P * F32
    return (cf, cb), (af, ab)


def least_time(flops: float, nbytes: float, pk: dict) -> float:
    return max(flops / pk["flops"], nbytes / pk["hbm_bytes_s"])
