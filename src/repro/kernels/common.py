"""Shared Pallas helpers: interpret-mode detection, compiler params."""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def default_interpret() -> bool:
    """Pallas TPU kernels run compiled on TPU, interpret elsewhere (CPU CI)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` means "interpret only where no TPU exists"."""
    return default_interpret() if interpret is None else interpret


def tpu_compiler_params(dimension_semantics) -> pltpu.CompilerParams:
    """Mosaic compiler params: the grid's parallel/arbitrary semantics."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)
