"""Pallas kernels of the main path: SWE flux sweeps, Matérn-5/2, attention.

Each kernel runs the way its platform allows, with no flag to set: Mosaic
compiles it on a TPU, and the Pallas interpreter runs it anywhere else (the
CPU the tests run on).  A kernel is never interpreted on a TPU.
"""
from __future__ import annotations

from typing import Callable

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel: Callable, **kwargs) -> Callable:
    """``pl.pallas_call`` whose interpret mode follows the platform.

    Returns a callable over the kernel's operands.  The choice is made by
    ``lax.platform_dependent`` when the program is lowered, for the platform
    it is lowered for, so it holds inside ``jit``/``vmap``/``shard_map`` and
    for ahead-of-time compiles against a described TPU.
    """
    compiled = pl.pallas_call(kernel, interpret=False, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(
            *args, tpu=compiled, default=interpreted
        )

    return call
