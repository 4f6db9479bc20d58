"""jit'd public wrapper for the Matérn-5/2 Pallas kernel.

Compiled on a TPU, interpreted elsewhere (see :mod:`repro.kernels`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .matern import matern52_pallas


@jax.jit
def matern52(x1: jax.Array, x2: jax.Array, params):
    """Drop-in replacement for :func:`repro.core.gp.matern52`.

    ``params`` is a :class:`repro.core.gp.GPParams`; ARD scaling happens here
    so the Pallas kernel stays a pure geometry primitive.
    """
    ls = jnp.exp(params.log_lengthscales)
    a = x1 / ls
    b = x2 / ls
    return matern52_pallas(a, b, jnp.exp(params.log_outputscale))
