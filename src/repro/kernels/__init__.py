"""Pallas TPU kernels for the GFlowNet hot path (``ops.py`` holds the jitted
entries, ``ref.py`` the pure-jnp oracles the tests compare against)."""
from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Kernel execution mode: ``None`` lowers through Mosaic on the TPU and
    runs the Pallas interpreter on every other platform; an explicit bool
    wins (tests that want the interpreter, or compiles for a described TPU
    topology from a CPU host)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def round_up(n: int, m: int) -> int:
    """``n`` rounded up to a multiple of ``m`` (block and tile padding)."""
    return -(-n // m) * m
