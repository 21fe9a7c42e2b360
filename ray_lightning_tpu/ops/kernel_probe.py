"""Shared switches for the optional Pallas kernels.

Every optional kernel in :mod:`ray_lightning_tpu.ops` has a numerically
identical XLA/scan path.  Which of the two a call takes is a function of
its shapes, dtype, mesh and ``RLT_DISABLE_KERNELS`` — never of whether a
trial compile happened to succeed: a kernel the chip's compiler refuses
is an error that reaches the user, and ``tests/test_chip_compile.py``
compiles each kernel for the chip at the main path's shapes so that a
refusal is found before a chip is.

Off-TPU the kernels run under the Pallas interpreter.
"""

from __future__ import annotations

import os

import jax

__all__ = ["kernel_family_disabled", "_interpret"]


def kernel_family_disabled(family: str) -> bool:
    """A/B switch for on-hardware kernel experiments: set
    ``RLT_DISABLE_KERNELS=ce,ln,flash,paged`` (any subset) to force the
    XLA path for those kernel families.  Read per call, so one
    process can bench both arms."""
    raw = os.environ.get("RLT_DISABLE_KERNELS", "")
    return family in {s.strip() for s in raw.split(",") if s.strip()}


def _interpret() -> bool:
    """Mosaic compiles only for TPU; every other backend (the CPU test
    meshes) runs the kernels under the Pallas interpreter — the single
    source for that decision across all optional kernels."""
    return jax.default_backend() != "tpu"
