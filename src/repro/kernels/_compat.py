"""Compiler parameters shared by every Pallas TPU kernel.

Every `pallas_call` takes its parameters from `compiler_params`, so each
kernel is compiled with the scoped-VMEM limit that `core.tiling` plans its
blocks against (`tiling.DEFAULT_VMEM_BUDGET`): a block the planner accepts is
a block the compiler is allowed to place.
"""

from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu

from repro.core import tiling


def compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(
        dimension_semantics=dimension_semantics,
        vmem_limit_bytes=tiling.DEFAULT_VMEM_BUDGET,
    )
