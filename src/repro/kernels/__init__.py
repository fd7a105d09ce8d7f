# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Kernel-backend selection shared by the serving stack.

One vocabulary everywhere (``EngineConfig.kernel_backend``, the decode /
prefill bodies, the SSD scan call sites):

  * ``"jnp"``              — pure-jnp paths (the bit-exact reference).
  * ``"pallas"``           — compiled Pallas kernels; TPU only.  Off the
    TPU it raises rather than fall back, so a run that lost its chip
    cannot pass on the interpreter.
  * ``"pallas-interpret"`` — the same kernels through the Pallas
    interpreter (CPU runners, CI).
"""

from __future__ import annotations

import os
from typing import Tuple

import jax

KERNEL_BACKENDS = ("jnp", "pallas", "pallas-interpret")


def check_kernel_backend(backend: str) -> str:
    if backend not in KERNEL_BACKENDS:
        raise ValueError(f"unknown kernel_backend {backend!r}: valid values "
                         f"are {KERNEL_BACKENDS}")
    return backend


def default_kernel_backend() -> str:
    """Process-wide default, overridable via ``REPRO_KERNEL_BACKEND`` (the
    CI tier-1 variant sets it to ``pallas-interpret`` so the whole serving
    stack — engine AND the reference step builders tests compare against —
    flips together)."""
    return check_kernel_backend(
        os.environ.get("REPRO_KERNEL_BACKEND", "jnp"))


def resolve_kernel_backend(backend: str) -> Tuple[bool, bool]:
    """backend name -> ``(use_pallas, interpret)``."""
    check_kernel_backend(backend)
    if backend == "jnp":
        return False, False
    if backend == "pallas" and jax.default_backend() != "tpu":
        raise ValueError(
            f"kernel_backend 'pallas' compiles its kernels for the TPU, but "
            f"the default backend is {jax.default_backend()!r}; use "
            f"'pallas-interpret' to run them through the interpreter")
    return True, backend == "pallas-interpret"
