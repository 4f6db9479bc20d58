"""jit'd wrappers: full SWE time steps built from the Pallas sweeps.

``swe_step`` is the drop-in single-grid replacement for
:func:`repro.swe.solver.step` (two strip sweeps + a transpose for y).
``swe_step_batched`` advances a whole stacked ``(B, ny, nx)`` batch in one
launch: by default through the fused x+y kernel (no transposes at all),
else through the batch-axis strip sweeps (DESIGN.md §7).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.swe.solver import H_EPS, SWEConfig, SWEState

from .swe_flux import (
    FUSED_VMEM_BUDGET_BYTES,
    swe_fused_step_pallas,
    swe_sweep_pallas,
)


def _strip_step(
    state: SWEState, b: jax.Array, dt: float, cfg: SWEConfig
) -> SWEState:
    """One step via two strip sweeps; axis-generic over a leading batch dim.

    ``state`` arrays are ``(ny, nx)`` or ``(B, ny, nx)`` with ``b``
    broadcast to match; the last two axes are always (row, column), so the
    same padding/transpose bookkeeping serves both the per-sample path and
    the batch-grid-axis path (no hand-mirrored copies to keep in sync).
    """
    h, hu, hv = state
    padx = lambda q: jnp.pad(
        q, [(0, 0)] * (q.ndim - 1) + [(1, 1)], mode="edge"
    )
    swapT = lambda q: q.swapaxes(-1, -2)

    # x sweep
    dhx, dhux, dhvx = swe_sweep_pallas(
        padx(h), padx(hu), padx(hv), padx(b), g=cfg.g, dx=cfg.dx,
    )
    # y sweep: transpose + swap (u, v)
    dhyT, dhvyT, dhuyT = swe_sweep_pallas(
        padx(swapT(h)), padx(swapT(hv)), padx(swapT(hu)), padx(swapT(b)),
        g=cfg.g, dx=cfg.dy,
    )
    dhy, dhuy, dhvy = swapT(dhyT), swapT(dhuyT), swapT(dhvyT)

    h_new = jnp.maximum(h - dt * (dhx + dhy), 0.0)
    hu_new = hu - dt * (dhux + dhuy)
    hv_new = hv - dt * (dhvx + dhvy)
    wet = h_new > H_EPS
    return SWEState(
        h_new, jnp.where(wet, hu_new, 0.0), jnp.where(wet, hv_new, 0.0)
    )


def swe_step(
    state: SWEState,
    b: jax.Array,
    dt: float,
    *,
    cfg: SWEConfig,
) -> SWEState:
    """Drop-in replacement for :func:`repro.swe.solver.step`."""
    return _strip_step(state, b, dt, cfg)


def _fused_fits(cfg: SWEConfig, itemsize: int = 4) -> bool:
    return 7 * (cfg.ny + 2) * (cfg.nx + 2) * itemsize <= FUSED_VMEM_BUDGET_BYTES


def swe_step_batched(
    state: SWEState,
    b: jax.Array,
    dt: float,
    *,
    cfg: SWEConfig,
    fused: bool = True,
) -> SWEState:
    """One time step for a stacked batch: state arrays are ``(B, ny, nx)``.

    ``fused=True`` (default) runs the fused x+y kernel — grid ``(B,)``, one
    launch per step, zero transposes; it falls back to the batch-axis
    strip sweeps automatically when the per-member plane would not fit the
    fused kernel's VMEM budget (large grids).
    """
    h, hu, hv = state
    if fused and _fused_fits(cfg, h.dtype.itemsize):
        padb = lambda q: jnp.pad(q, ((0, 0), (1, 1), (1, 1)), mode="edge")
        b2 = jnp.pad(b, ((1, 1), (1, 1)), mode="edge")
        h_new, hu_new, hv_new = swe_fused_step_pallas(
            padb(h), padb(hu), padb(hv), b2,
            g=cfg.g, dx=cfg.dx, dy=cfg.dy, dt=dt,
        )
        return SWEState(h_new, hu_new, hv_new)
    # strip sweeps with the batch grid axis (same body as swe_step)
    return _strip_step(
        state, jnp.broadcast_to(b[None], h.shape), dt, cfg
    )
