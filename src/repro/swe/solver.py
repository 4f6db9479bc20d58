"""Well-balanced finite-volume shallow-water solver in JAX (paper §3).

ExaHyPE's scheme is ADER-DG with a-posteriori FV subcell limiting; Fig. 3 of
the paper shows the FV layer owning exactly the regions that matter for the
inverse problem (wavefront, coast, source region).  We implement that robust
layer globally (DESIGN.md §7.2): first-order hydrostatic-reconstruction
finite volumes (Audusse et al. 2004) with a Rusanov interface flux — the
same well-balancedness and positivity properties the paper requires:

  * lake-at-rest ``(u, v) = 0, eta = const`` is preserved exactly over
    arbitrary bathymetry (paper §3.2 calls this out explicitly);
  * water depth stays non-negative (wet/dry fronts handled by the
    hydrostatic reconstruction + desingularised velocities, the same
    one-sided-draining cap idea as the paper's augmented Riemann solver);
  * bathymetry is carried with the state, mirroring the paper's choice to
    keep ``b`` as an unknown so that balance is not destroyed.

The state is ``(h, hu, hv)`` on a structured cell-centred grid with static
``b``.  Time stepping is ``lax.scan`` with a fixed CFL-derived dt so the
whole solve is one XLA program (TPU-friendly: no host round trips).  The
per-step stencil update also exists as a Pallas TPU kernel
(``repro.kernels.swe_flux``) with this module as its oracle.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

G = 9.81  # m/s^2
H_EPS = 1e-3  # wet/dry threshold [m]


class SWEState(NamedTuple):
    h: jax.Array  # (ny, nx) water depth >= 0
    hu: jax.Array  # (ny, nx) x-momentum
    hv: jax.Array  # (ny, nx) y-momentum


@dataclass(frozen=True)
class SWEConfig:
    nx: int
    ny: int
    dx: float  # [m]
    dy: float  # [m]
    t_end: float  # [s]
    cfl: float = 0.45
    g: float = G
    dt_override: Optional[float] = None


def desingularized_velocity(h: jax.Array, hq: jax.Array, eps: float = H_EPS) -> jax.Array:
    """u = hu/h without dividing by ~0 in dry cells (Kurganov-Petrova)."""
    h4 = h**4
    return jnp.sqrt(2.0) * h * hq / jnp.sqrt(h4 + jnp.maximum(h4, eps**4))


def _interface_flux_1d(hL, uL, vL, hR, uR, vR, g):
    """Rusanov flux through an x-interface for reconstructed states.

    The momentum flux is returned *without* its pressure part: on a 7 km
    ocean the g/2 h^2 terms are ~2.4e8 while the net momentum tendency is
    O(1e2), so forming them per-face and differencing loses ~7e5 x eps_fp32
    — fatal on TPUs (fp32 only).  The caller assembles the pressure+source
    contribution in deviation form (difference-of-reconstructions times
    their sum), which is algebraically identical and fp32-stable
    (DESIGN.md §7: hardware adaptation).
    """
    huL, hvL = hL * uL, hL * vL
    huR, hvR = hR * uR, hR * vR
    # Safe sqrt: d/dh sqrt(g h) -> inf at dry cells NaNs the whole backward
    # pass (UM-Bridge exposes gradients, paper §2.1 — keep F differentiable).
    cL = jnp.abs(uL) + jnp.where(hL > 0, jnp.sqrt(g * jnp.where(hL > 0, hL, 1.0)), 0.0)
    cR = jnp.abs(uR) + jnp.where(hR > 0, jnp.sqrt(g * jnp.where(hR > 0, hR, 1.0)), 0.0)
    a = jnp.maximum(cL, cR)
    f0 = 0.5 * (huL + huR) - 0.5 * a * (hR - hL)
    f1 = 0.5 * (huL * uL + huR * uR) - 0.5 * a * (huR - huL)  # advective only
    f2 = 0.5 * (hvL * uL + hvR * uR) - 0.5 * a * (hvR - hvL)
    return f0, f1, f2


def _x_update(h, hu, hv, b, dx, g):
    """Flux-difference + well-balanced source along x (axis=1).

    Hydrostatic reconstruction: at interface i+1/2 with left cell L and
    right cell R,
        b* = max(b_L, b_R)
        h_L* = max(0, h_L + b_L - b*),   h_R* = max(0, h_R + b_R - b*)
    The momentum update gains the pressure correction
        + g/2 (h_i^2 - h_{i-1/2,R}*^2)  - g/2 (h_{i+1/2,L}*^2 - h_i^2)
    which cancels the flux imbalance exactly at lake-at-rest.
    """
    # Zero-gradient (outflow) ghost cells.
    pad = lambda q: jnp.pad(q, ((0, 0), (1, 1)), mode="edge")
    hp, hup, hvp, bp = pad(h), pad(hu), pad(hv), pad(b)

    bL, bR = bp[:, :-1], bp[:, 1:]
    bstar = jnp.maximum(bL, bR)
    hL = jnp.maximum(hp[:, :-1] + bL - bstar, 0.0)
    hR = jnp.maximum(hp[:, 1:] + bR - bstar, 0.0)
    # Momenta rescaled to the reconstructed depth (velocity preserved).
    uL = desingularized_velocity(hp[:, :-1], hup[:, :-1])
    vL = desingularized_velocity(hp[:, :-1], hvp[:, :-1])
    uR = desingularized_velocity(hp[:, 1:], hup[:, 1:])
    vR = desingularized_velocity(hp[:, 1:], hvp[:, 1:])
    f0, f1, f2 = _interface_flux_1d(hL, uL, vL, hR, uR, vR, g)

    # Per-cell flux difference; interface j is between cells j-1 and j.
    dh = f0[:, 1:] - f0[:, :-1]
    dhu = f1[:, 1:] - f1[:, :-1]
    dhv = f2[:, 1:] - f2[:, :-1]
    # Pressure + well-balanced source, assembled in deviation form.  The
    # Audusse update is
    #   dhu*dx = [f1 + g/2 hL*^2]_r - [f1 + g/2 hR*^2]_l
    #          + g/2 (h_i^2 - hLs^2) - g/2 (h_i^2 - hRs^2)
    # whose pressure part reduces to interface-local differences
    #   g/2 (hR*^2 - hL*^2)_r_face + g/2 (hR*^2 - hL*^2)_l_face ... grouped
    # as (small difference) x (large sum) to avoid catastrophic fp32
    # cancellation of the ~g/2 h^2 ~ 2.4e8 terms:
    hLr = hL[:, 1:]  # own reconstruction at right face (L side of face)
    hRr = hR[:, 1:]  # neighbour reconstruction at right face
    hLl = hL[:, :-1]  # neighbour reconstruction at left face
    hRl = hR[:, :-1]  # own reconstruction at left face (R side of face)
    press = 0.25 * g * ((hRr - hLr) * (hRr + hLr) + (hRl - hLl) * (hRl + hLl))
    dhu = dhu + press
    return dh / dx, dhu / dx, dhv / dx


def _y_update(h, hu, hv, b, dy, g):
    """Same as :func:`_x_update` along y, by transposition + (u,v) swap."""
    dh, dhv, dhu = _x_update(h.T, hv.T, hu.T, b.T, dy, g)
    return dh.T, dhu.T, dhv.T


def step(state: SWEState, b: jax.Array, cfg: SWEConfig, dt: float) -> SWEState:
    """One unsplit forward-Euler step of the well-balanced FV scheme."""
    h, hu, hv = state
    dhx, dhux, dhvx = _x_update(h, hu, hv, b, cfg.dx, cfg.g)
    dhy, dhuy, dhvy = _y_update(h, hu, hv, b, cfg.dy, cfg.g)
    h_new = h - dt * (dhx + dhy)
    hu_new = hu - dt * (dhux + dhuy)
    hv_new = hv - dt * (dhvx + dhvy)
    # Positivity + drying: clamp tiny/negative depths, kill momentum there
    # (the paper's 'no FV update removes more water than locally available').
    h_new = jnp.maximum(h_new, 0.0)
    wet = h_new > H_EPS
    hu_new = jnp.where(wet, hu_new, 0.0)
    hv_new = jnp.where(wet, hv_new, 0.0)
    return SWEState(h_new, hu_new, hv_new)


def stable_dt(cfg: SWEConfig, h_max: float, u_margin: float = 15.0) -> float:
    """CFL-derived fixed dt (static step count keeps the solve one program)."""
    c = math.sqrt(cfg.g * max(h_max, 1.0)) + u_margin
    return cfg.cfl * min(cfg.dx, cfg.dy) / c


def pow2_batch(n: int) -> int:
    """Next power of two >= n — the AOT executable-cache bucketing."""
    if n < 1:
        raise ValueError("batch size must be >= 1")
    return 1 << (n - 1).bit_length()


class AOTBatchCache:
    """Power-of-two padded, per-``(*key, B)`` AOT executable cache.

    The one home of the batched-dispatch compile bookkeeping (DESIGN.md
    §7.2), shared by the solver-level and scenario-level batch factories:
    lowers ``stacked_fn`` once per padded batch size, reuses the
    executable for every later batch that buckets to the same size, and
    (with ``donate=True``) donates the stacked input buffer — staging a
    private copy first when the caller handed us a live jax array, since
    donation deletes the buffer.

    ``pad``: ``"zeros"`` fills padding members with zeros, ``"repeat"``
    replicates member 0 (use when zeros are not a valid input).  Calling
    returns ``(result_pytree, n)`` with the *padded* leading axis; the
    caller slices back to ``n``.

    ``stacked`` may be any pytree whose leaves all carry the batch as
    their leading axis (e.g. a device-resident ensemble state) —
    ``dtype=None`` then preserves each leaf's own dtype instead of casting
    (RNG keys stay uint32, counters stay int32).

    ``name``, when given, names the compiled programs (``jit_<name>``), so
    a profiler trace tells them apart from other programs built from a
    function of the same name.
    """

    def __init__(
        self,
        stacked_fn: Callable,
        *,
        key: Tuple,
        dtype=None,
        donate: bool = False,
        pad: str = "zeros",
        name: Optional[str] = None,
    ) -> None:
        if pad not in ("zeros", "repeat"):
            raise ValueError(f"unknown pad mode '{pad}'")
        if name is not None:
            fn = stacked_fn

            def stacked_fn(stacked):
                return fn(stacked)

            stacked_fn.__name__ = stacked_fn.__qualname__ = name
        self.stacked_fn = stacked_fn
        self.key = tuple(key)
        self.dtype = dtype
        self.donate = donate
        self.pad = pad
        self.executables: dict = {}

    def __call__(self, stacked):
        orig, treedef = jax.tree_util.tree_flatten(stacked)
        leaves = [
            jnp.asarray(x) if self.dtype is None else jnp.asarray(x, self.dtype)
            for x in orig
        ]
        if self.donate:
            # Donation deletes the input buffer: stage a private copy when
            # the caller handed us a live jax array we would otherwise kill.
            leaves = [
                jnp.array(x, copy=True) if x is a else x
                for x, a in zip(leaves, orig)
            ]
        n = leaves[0].shape[0]
        n_pad = pow2_batch(n)
        key = (*self.key, n_pad)
        exe = self.executables.get(key)
        if exe is None:
            specs = treedef.unflatten(
                [
                    jax.ShapeDtypeStruct((n_pad, *x.shape[1:]), x.dtype)
                    for x in leaves
                ]
            )
            jitted = jax.jit(
                self.stacked_fn, donate_argnums=(0,) if self.donate else ()
            )
            exe = jitted.lower(specs).compile()
            self.executables[key] = exe
        if n_pad != n:

            def fill(x):
                shape = (n_pad - n, *x.shape[1:])
                pad = (
                    jnp.zeros(shape, x.dtype)
                    if self.pad == "zeros"
                    else jnp.broadcast_to(x[:1], shape)
                )
                return jnp.concatenate([x, pad])

            leaves = [fill(x) for x in leaves]
        return exe(treedef.unflatten(leaves)), n


def make_solver(
    cfg: SWEConfig,
    b: jax.Array,
    probe_ij: Sequence[Tuple[int, int]],
    *,
    use_pallas: bool = False,
    batch: bool = False,
) -> Callable:
    """Build ``solve(eta0) -> (eta_series, final_state)``.

    ``eta0`` is the initial sea-surface displacement (SSHA) added to the
    lake-at-rest depth; ``eta_series`` is (n_steps, n_probes) SSHA at the
    probes — everything the observation operator needs.

    With ``batch=True`` the returned callable instead takes a stacked
    ``(B, ny, nx)`` displacement array and returns
    ``((B, n_steps, n_probes) series, batched final state)``: the whole
    batch advances in ONE fused time loop (a batched Pallas sweep when
    ``use_pallas``, a ``vmap`` of :func:`step` otherwise), AOT-compiled
    per batch size with the input buffer donated and cached under
    ``(grid shape, B)`` after power-of-two padding — see
    ``solve.executables``.  Per-member results are bit-identical (fp32) to
    the unbatched solver: the batch dimension only adds a leading axis to
    the same elementwise arithmetic.
    """
    b = jnp.asarray(b)
    h_rest = jnp.maximum(-b, 0.0)
    h_max = float(jnp.max(h_rest))
    if cfg.dt_override is not None:
        # NOT `dt_override or stable_dt(...)`: 0.0 is falsy, and silently
        # replacing an (invalid) explicit override with the CFL dt masks
        # the configuration error — reject it instead.
        if cfg.dt_override <= 0.0:
            raise ValueError(
                f"dt_override must be positive, got {cfg.dt_override}"
            )
        dt = cfg.dt_override
    else:
        dt = stable_dt(cfg, h_max)
    n_steps = int(math.ceil(cfg.t_end / dt))
    pi = jnp.asarray([ij[0] for ij in probe_ij])
    pj = jnp.asarray([ij[1] for ij in probe_ij])

    if use_pallas:
        from repro.kernels.swe_flux import ops as swe_ops

        step_fn = partial(swe_ops.swe_step, cfg=cfg)
    else:
        step_fn = None

    def solve(eta0: jax.Array):
        h0 = jnp.maximum(h_rest + eta0, 0.0)
        # Displacement only applies to wet cells (paper: filtered bed change).
        h0 = jnp.where(h_rest > H_EPS, h0, h_rest)
        state = SWEState(h0, jnp.zeros_like(h0), jnp.zeros_like(h0))

        def body(state, _):
            if step_fn is not None:
                new = step_fn(state, b, dt)
            else:
                new = step(state, b, cfg, dt)
            eta = new.h + b  # SSHA where wet (b<0 ocean): eta = h + b
            return new, eta[pi, pj]

        final, series = jax.lax.scan(body, state, None, length=n_steps)
        return series, final

    solve.n_steps = n_steps
    solve.dt = dt
    if not batch:
        return solve
    return _make_batched_solver(cfg, b, pi, pj, solve, n_steps, dt, use_pallas)


def _make_batched_solver(
    cfg: SWEConfig,
    b: jax.Array,
    pi: jax.Array,
    pj: jax.Array,
    solve: Callable,
    n_steps: int,
    dt: float,
    use_pallas: bool,
) -> Callable:
    """Stacked-batch wrapper: AOT ``vmap`` executables behind a size cache.

    The time loop is still one ``lax.scan``; the batch is a leading axis
    carried through every step, so the whole batch is ONE XLA program per
    step (and, with ``use_pallas``, one Pallas launch per fused sweep —
    the kernel's batch grid axis).  Executables are ``lower().compile()``d
    once per ``(grid shape, padded B)`` with the stacked input donated,
    then reused for every later batch that pads to the same size.
    """
    if use_pallas:
        from repro.kernels.swe_flux import ops as swe_ops

        def step_batch(state: SWEState) -> SWEState:
            return swe_ops.swe_step_batched(state, b, dt, cfg=cfg)
    else:
        step_one = lambda s: step(s, b, cfg, dt)
        step_batch = jax.vmap(step_one)

    h_rest = jnp.maximum(-b, 0.0)
    dtype = h_rest.dtype

    def solve_stacked(eta0_b: jax.Array):
        h0 = jnp.maximum(h_rest[None] + eta0_b, 0.0)
        h0 = jnp.where(h_rest[None] > H_EPS, h0, h_rest[None])
        state = SWEState(h0, jnp.zeros_like(h0), jnp.zeros_like(h0))

        def body(state, _):
            new = step_batch(state)
            eta = new.h + b[None]
            return new, eta[:, pi, pj]

        final, series = jax.lax.scan(body, state, None, length=n_steps)
        return jnp.moveaxis(series, 0, 1), final  # (B, n_steps, n_probes)

    # Zero-displacement padding members are lake-at-rest solves.
    cache = AOTBatchCache(
        solve_stacked, key=(cfg.ny, cfg.nx), dtype=dtype, donate=True,
        pad="zeros",
    )

    def solve_batch(eta0_b: jax.Array):
        if jnp.ndim(eta0_b) != 3:
            raise ValueError(
                f"batched solver wants (B, ny, nx), got {jnp.shape(eta0_b)}"
            )
        (series, final), n = cache(eta0_b)
        return series[:n], SWEState(final.h[:n], final.hu[:n], final.hv[:n])

    solve_batch.n_steps = n_steps
    solve_batch.dt = dt
    solve_batch.executables = cache.executables
    solve_batch.solve_one = solve
    return solve_batch


def lake_at_rest_error(cfg: SWEConfig, b: jax.Array, n_steps: int = 50) -> float:
    """Max |eta| + |momentum| drift from the lake-at-rest steady state."""
    b = jnp.asarray(b)
    h = jnp.maximum(-b, 0.0)
    state = SWEState(h, jnp.zeros_like(h), jnp.zeros_like(h))
    dt = stable_dt(cfg, float(jnp.max(h)))

    def body(s, _):
        return step(s, b, cfg, dt), None

    final, _ = jax.lax.scan(body, state, None, length=n_steps)
    wet = h > H_EPS
    eta_err = jnp.max(jnp.abs(jnp.where(wet, (final.h + b) - (h + b), 0.0)))
    u_err = jnp.max(jnp.abs(desingularized_velocity(final.h, final.hu)))
    v_err = jnp.max(jnp.abs(desingularized_velocity(final.h, final.hv)))
    return float(eta_err + u_err + v_err)
