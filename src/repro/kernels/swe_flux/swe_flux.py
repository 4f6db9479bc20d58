"""Pallas TPU kernel: well-balanced SWE flux sweep (the PDE hot spot).

The paper's forward model spends its time in the per-cell flux/limiter
update (ExaHyPE's FV subcell layer).  TPU adaptation (DESIGN.md §2): instead
of the CPU/MPI cell-loop, the sweep is tiled into VMEM row strips:

  * the x-sweep is embarrassingly parallel across rows, so each grid step
    owns a (block_rows, nx+2) strip — the one-cell halo lives *inside* the
    strip (edge-padded by the wrapper), which avoids overlapping BlockSpecs
    (TPU pipelining wants disjoint tiles);
  * all reconstruction/flux math is vectorised elementwise over the strip —
    VPU work with unit-stride lanes along x; the only lane-misaligned ops
    are two static 1-cell shifts, which Mosaic lowers to cheap roll ops;
  * the y-sweep reuses the same kernel on the transposed state (u <-> v),
    so one kernel serves both directions;
  * fp32 throughout (wave heights ~1e-1 m on 7e3 m depths need it).

Batched evaluation (DESIGN.md §7) adds two variants:

  * a **batch grid axis**: :func:`swe_sweep_pallas` accepts stacked
    ``(B, ny, nx+2)`` strips and runs grid ``(B, ny/block_rows)`` — one
    kernel launch covers the whole coalesced batch;
  * a **fused x+y sweep** (:func:`swe_fused_step_pallas`): one kernel per
    batch member owns the fully (1-cell) padded grid and performs both
    directional sweeps *and* the forward-Euler update in place, removing
    the four transposes per step that the transpose-and-reuse trick costs
    on the batched hot path.  The y-direction flux is the same Rusanov
    math with the roles of (u, v) and the slicing axis swapped.

VMEM: the strip sweep holds 4 input + 3 output strips of (8, nx+2) fp32
~ 0.25 MiB at nx = 1024; the fused kernel holds 7 full (ny+2, nx+2)
planes per member — ~0.27 MiB at 96x96, so it targets the MLDA-scale
grids (the wrapper asserts the plane fits comfortably in VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_call

H_EPS = 1e-3
DEFAULT_BLOCK_ROWS = 8
# Conservative per-member VMEM budget for the fused kernel: 7 fp32 planes
# plus reconstruction temporaries must fit in ~16 MiB/core.
FUSED_VMEM_BUDGET_BYTES = 8 * 2**20


def _desing_vel(h, hq, eps=H_EPS):
    h4 = h**4
    return jnp.sqrt(2.0) * h * hq / jnp.sqrt(h4 + jnp.maximum(h4, eps**4))


def _sweep_math(h, hu, hv, b, *, g, dx):
    """Directional flux sweep over an edge-padded strip (axis -1 = normal).

    Shared by the strip kernel (2D refs), its batched variant (3D refs)
    and the fused kernel (which calls it once per direction).  Returns the
    per-cell flux-difference tendencies for the strip interior along the
    normal axis: shapes ``(..., n-2)`` for ``(..., n)`` inputs.
    """
    # Interface states: L = cell j, R = cell j+1  (n-1 interfaces).
    bL, bR = b[..., :-1], b[..., 1:]
    bstar = jnp.maximum(bL, bR)
    hL = jnp.maximum(h[..., :-1] + bL - bstar, 0.0)
    hR = jnp.maximum(h[..., 1:] + bR - bstar, 0.0)
    uL = _desing_vel(h[..., :-1], hu[..., :-1])
    vL = _desing_vel(h[..., :-1], hv[..., :-1])
    uR = _desing_vel(h[..., 1:], hu[..., 1:])
    vR = _desing_vel(h[..., 1:], hv[..., 1:])
    huL, hvL = hL * uL, hL * vL
    huR, hvR = hR * uR, hR * vR

    # Rusanov flux; momentum flux is advective-only — pressure + source are
    # assembled per cell in the fp32-stable deviation form (see solver.py).
    # Safe sqrt at dry cells keeps the sweep differentiable (as in solver.py).
    cL = jnp.where(hL > 0, jnp.sqrt(g * jnp.where(hL > 0, hL, 1.0)), 0.0)
    cR = jnp.where(hR > 0, jnp.sqrt(g * jnp.where(hR > 0, hR, 1.0)), 0.0)
    a = jnp.maximum(jnp.abs(uL) + cL, jnp.abs(uR) + cR)
    f0 = 0.5 * (huL + huR) - 0.5 * a * (hR - hL)
    f1 = 0.5 * (huL * uL + huR * uR) - 0.5 * a * (huR - huL)
    f2 = 0.5 * (hvL * uL + hvR * uR) - 0.5 * a * (hvR - hvL)

    # Per-cell update for interior cells (1..n-2 of the padded strip).
    dh = f0[..., 1:] - f0[..., :-1]
    dhu = f1[..., 1:] - f1[..., :-1]
    dhv = f2[..., 1:] - f2[..., :-1]
    # Well-balanced pressure in deviation form: per-face (small diff) x sum.
    hLr, hRr = hL[..., 1:], hR[..., 1:]
    hLl, hRl = hL[..., :-1], hR[..., :-1]
    dhu = dhu + 0.25 * g * (
        (hRr - hLr) * (hRr + hLr) + (hRl - hLl) * (hRl + hLl)
    )
    return dh / dx, dhu / dx, dhv / dx


def _sweep_kernel(h_ref, hu_ref, hv_ref, b_ref, dh_ref, dhu_ref, dhv_ref, *, g, dx):
    """One x-direction flux sweep over an edge-padded row strip."""
    dh, dhu, dhv = _sweep_math(
        h_ref[...], hu_ref[...], hv_ref[...], b_ref[...], g=g, dx=dx
    )
    dh_ref[...] = dh
    dhu_ref[...] = dhu
    dhv_ref[...] = dhv


def swe_sweep_pallas(
    h: jax.Array,  # (ny, nxp) or (B, ny, nxp), edge-padded in x (nxp = nx+2)
    hu: jax.Array,
    hv: jax.Array,
    b: jax.Array,  # (ny, nxp) / (B, ny, nxp); 2D b broadcasts over the batch
    *,
    g: float,
    dx: float,
    block_rows: int = DEFAULT_BLOCK_ROWS,
):
    """Directional flux sweep; with 3D inputs the grid gains a batch axis.

    The batched form runs grid ``(B, ny/block_rows)`` in a single
    ``pallas_call`` — one launch for the whole stacked batch instead of B
    sequential launches (the coalesced-dispatch hot path).
    """
    batched = h.ndim == 3
    if batched and b.ndim == 2:
        b = jnp.broadcast_to(b[None], h.shape)
    *lead, ny, nxp = h.shape
    br = min(block_rows, ny)
    ny_pad = pl.cdiv(ny, br) * br
    if ny_pad != ny:
        pad = ([(0, 0)] if batched else []) + [(0, ny_pad - ny), (0, 0)]
        h, hu, hv, b = (jnp.pad(x, pad, mode="edge") for x in (h, hu, hv, b))

    kernel = functools.partial(_sweep_kernel, g=float(g), dx=float(dx))
    if batched:
        B = lead[0]
        grid = (B, ny_pad // br)
        in_spec = pl.BlockSpec((1, br, nxp), lambda n, i: (n, i, 0))
        out_spec = pl.BlockSpec((1, br, nxp - 2), lambda n, i: (n, i, 0))
        out_shape = [jax.ShapeDtypeStruct((B, ny_pad, nxp - 2), h.dtype)] * 3
    else:
        grid = (ny_pad // br,)
        in_spec = pl.BlockSpec((br, nxp), lambda i: (i, 0))
        out_spec = pl.BlockSpec((br, nxp - 2), lambda i: (i, 0))
        out_shape = [jax.ShapeDtypeStruct((ny_pad, nxp - 2), h.dtype)] * 3
    dh, dhu, dhv = pallas_call(
        kernel,
        grid=grid,
        in_specs=[in_spec] * 4,
        out_specs=[out_spec] * 3,
        out_shape=out_shape,
    )(h, hu, hv, b)
    if batched:
        return dh[:, :ny], dhu[:, :ny], dhv[:, :ny]
    return dh[:ny], dhu[:ny], dhv[:ny]


def _fused_kernel(
    h_ref, hu_ref, hv_ref, b_ref,
    h_out, hu_out, hv_out,
    *, g, dx, dy, dt,
):
    """Fused x+y sweep + forward-Euler update for ONE batch member.

    Inputs are the member's fully edge-padded planes ``(1, ny+2, nx+2)``.
    The x-sweep slices along the lane axis; the y-sweep runs the *same*
    Rusanov/hydrostatic math along the sublane axis with the roles of
    ``(hu, hv)`` swapped — no transposes, no extra pallas launches.
    Outputs are the updated interior ``(1, ny, nx)`` state with the
    positivity clamp and wet-cell momentum mask applied in-kernel.
    """
    h, hu, hv, b = h_ref[0], hu_ref[0], hv_ref[0], b_ref[0]

    # x-sweep over interior rows (axis -1 is already the normal axis).
    dhx, dhux, dhvx = _sweep_math(
        h[1:-1], hu[1:-1], hv[1:-1], b[1:-1], g=g, dx=dx
    )
    # y-sweep over interior columns: transpose-free — slice along axis 0 by
    # handing _sweep_math the y-normal layout via swapaxes views.  Mosaic
    # lowers the static swaps into the slicing, and (u, v) swap roles.
    hT = h[:, 1:-1].swapaxes(0, 1)
    huT = hu[:, 1:-1].swapaxes(0, 1)
    hvT = hv[:, 1:-1].swapaxes(0, 1)
    bT = b[:, 1:-1].swapaxes(0, 1)
    dhyT, dhvyT, dhuyT = _sweep_math(hT, hvT, huT, bT, g=g, dx=dy)
    dhy = dhyT.swapaxes(0, 1)
    dhuy = dhuyT.swapaxes(0, 1)
    dhvy = dhvyT.swapaxes(0, 1)

    hi = h[1:-1, 1:-1]
    hui = hu[1:-1, 1:-1]
    hvi = hv[1:-1, 1:-1]
    h_new = jnp.maximum(hi - dt * (dhx + dhy), 0.0)
    hu_new = hui - dt * (dhux + dhuy)
    hv_new = hvi - dt * (dhvx + dhvy)
    wet = h_new > H_EPS
    h_out[0] = h_new
    hu_out[0] = jnp.where(wet, hu_new, 0.0)
    hv_out[0] = jnp.where(wet, hv_new, 0.0)


def swe_fused_step_pallas(
    h: jax.Array,  # (B, ny+2, nx+2) edge-padded in BOTH dims
    hu: jax.Array,
    hv: jax.Array,
    b: jax.Array,  # (ny+2, nx+2)
    *,
    g: float,
    dx: float,
    dy: float,
    dt: float,
):
    """One fused time step for a stacked batch: grid ``(B,)``, one launch.

    Each program owns one member's whole padded grid, so both directional
    sweeps and the Euler update happen without leaving VMEM — the four
    per-step transposes of the strip path are gone.  Returns the updated
    interior state ``(B, ny, nx)``.
    """
    B, nyp, nxp = h.shape
    plane_bytes = nyp * nxp * h.dtype.itemsize
    assert 7 * plane_bytes <= FUSED_VMEM_BUDGET_BYTES, (
        f"fused SWE kernel wants {7 * plane_bytes} B of VMEM per member "
        f"({nyp}x{nxp}); use the strip sweep for grids this large"
    )
    bb = jnp.broadcast_to(b[None], (B, nyp, nxp))
    kernel = functools.partial(
        _fused_kernel, g=float(g), dx=float(dx), dy=float(dy), dt=float(dt)
    )
    in_spec = pl.BlockSpec((1, nyp, nxp), lambda n: (n, 0, 0))
    out_spec = pl.BlockSpec((1, nyp - 2, nxp - 2), lambda n: (n, 0, 0))
    return pallas_call(
        kernel,
        grid=(B,),
        in_specs=[in_spec] * 4,
        out_specs=[out_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((B, nyp - 2, nxp - 2), h.dtype)] * 3,
    )(h, hu, hv, bb)
