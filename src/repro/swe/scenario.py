"""Tōhoku-like tsunami scenario (paper §3.2, §4).

The paper uses GEBCO bathymetry and NDBC DART buoy records (both behind
network downloads); we synthesise a trench-shaped bathymetry with the same
qualitative structure — a deep (~7 km) ocean plain, a subduction trench, a
continental shelf rising to dry land on the west — on the paper's domain
``[-499, 1299] x [-949, 849] km``, and generate observations from the *fine*
model at a known source (0, 0) plus measurement noise (DESIGN.md §7.3).

The inverse problem is identical in structure to the paper's: recover the
epicentre ``theta = (x0, y0)`` of the initial displacement from wave height
and arrival time at two DART-like probes, under a uniform prior on the
``[-200, 200]^2 km`` translation window (paper Fig. 4).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .solver import SWEConfig, make_solver

KM = 1000.0

# Paper domain (km).
DOMAIN_X = (-499.0, 1299.0)
DOMAIN_Y = (-949.0, 849.0)
# Displacement translation window (paper Fig. 4, red box).
PRIOR_X = (-200.0, 200.0)
PRIOR_Y = (-200.0, 200.0)
# DART-like probe positions (km) — offshore east of the source region with
# enough angular separation to triangulate (x0, y0); qualitatively matching
# DART 21418 (NE, near Japan) and 21419 (SE, further offshore).
PROBES_KM = ((480.0, 380.0), (700.0, -420.0))


@dataclass(frozen=True)
class TohokuScenario:
    """Grid-resolution-parameterised scenario; one instance per MLDA level."""

    nx: int = 96
    ny: int = 96
    t_end: float = 4.0 * 3600.0  # 4 h of simulated tsunami propagation
    amplitude: float = 5.0  # initial displacement height [m]
    sigma_km: float = 60.0  # displacement half-width
    arrival_threshold: float = 0.05  # [m] SSHA for arrival detection
    use_pallas: bool = False

    @property
    def cfg(self) -> SWEConfig:
        lx = (DOMAIN_X[1] - DOMAIN_X[0]) * KM
        ly = (DOMAIN_Y[1] - DOMAIN_Y[0]) * KM
        return SWEConfig(
            nx=self.nx, ny=self.ny, dx=lx / self.nx, dy=ly / self.ny, t_end=self.t_end
        )

    # -- geometry -----------------------------------------------------------
    def cell_centers(self) -> Tuple[jax.Array, jax.Array]:
        x = jnp.linspace(DOMAIN_X[0], DOMAIN_X[1], self.nx + 1)
        y = jnp.linspace(DOMAIN_Y[0], DOMAIN_Y[1], self.ny + 1)
        xc = 0.5 * (x[:-1] + x[1:])
        yc = 0.5 * (y[:-1] + y[1:])
        return xc, yc  # km

    def bathymetry(self) -> jax.Array:
        """Synthetic bed elevation b(x, y) [m] (negative = below sea level)."""
        xc, yc = self.cell_centers()
        X, Y = jnp.meshgrid(xc, yc)  # (ny, nx)
        # Deep plain ~ -7000 m; shelf rises towards the west (Japan side).
        plain = -7000.0
        shelf = 6950.0 * jnp.exp(-((X - DOMAIN_X[0]) / 220.0) ** 2)
        # Japan trench: a deeper trough running north-south near x ~ 120 km.
        trench = -1500.0 * jnp.exp(-(((X - 120.0) / 90.0) ** 2))
        # Gentle seamount ridge to keep the field non-trivial away from land.
        ridge = 800.0 * jnp.exp(-(((X - 700.0) / 260.0) ** 2 + ((Y - 250.0) / 330.0) ** 2))
        b = plain + shelf + trench + ridge
        # Dry land strip on the far west edge.
        b = jnp.where(X < DOMAIN_X[0] + 40.0, 50.0, b)
        return b

    def probe_indices(self) -> Sequence[Tuple[int, int]]:
        xc, yc = self.cell_centers()
        out = []
        for (px, py) in PROBES_KM:
            j = int(jnp.argmin(jnp.abs(xc - px)))
            i = int(jnp.argmin(jnp.abs(yc - py)))
            out.append((i, j))
        return out

    def displacement(self, theta: jax.Array) -> jax.Array:
        """Initial SSHA bump centred at theta = (x0, y0) km (paper §3.2)."""
        xc, yc = self.cell_centers()
        X, Y = jnp.meshgrid(xc, yc)
        r2 = ((X - theta[0]) ** 2 + (Y - theta[1]) ** 2) / self.sigma_km**2
        return self.amplitude * jnp.exp(-0.5 * r2)

    # -- forward model --------------------------------------------------------
    def build_forward(self) -> Callable:
        """theta (2,) -> observables (4,): [hmax_1, tarr_1, hmax_2, tarr_2].

        Arrival time is the soft first-crossing of the threshold (smooth in
        theta so derivative-based samplers work through UM-Bridge's gradient
        protocol), normalised to [0, 1] of the simulation window; wave
        heights are in metres.
        """
        solver = make_solver(
            self.cfg, self.bathymetry(), self.probe_indices(), use_pallas=self.use_pallas
        )
        n_steps = solver.n_steps
        dt = solver.dt
        thr = self.arrival_threshold
        t_norm = n_steps * dt

        def forward(theta: jax.Array) -> jax.Array:
            eta0 = self.displacement(theta)
            series, _ = solver(eta0)  # (n_steps, n_probes)
            hmax = jnp.max(series, axis=0)
            # Soft arrival time: integral of the not-yet-arrived indicator.
            # t_arr = sum_t dt * prod_{s<=t}(1 - sigmoid(k(eta_s - thr)))
            k = 40.0 / thr
            crossed = jax.nn.sigmoid(k * (series - thr))  # (T, P)
            not_yet = jnp.cumprod(1.0 - crossed, axis=0)
            t_arr = jnp.sum(not_yet, axis=0) * dt / t_norm
            return jnp.stack([hmax[0], t_arr[0], hmax[1], t_arr[1]])

        forward.n_steps = n_steps
        forward.dt = dt
        return forward

    def build_batch_forward(self) -> Callable:
        """thetas (B, 2) -> observables (B, 4) in ONE fused batched solve.

        The :class:`repro.balancer.types.BatchServer` handler for this
        level: the *whole* per-theta forward (displacement -> fused solve
        -> observation operator) is ``vmap``ped and AOT-compiled once per
        ``(grid shape, B)`` after power-of-two batch padding
        (:class:`repro.swe.solver.AOTBatchCache`).  Row ``i`` is
        bit-identical (fp32) to ``build_forward()(thetas[i])``: the batch
        axis only prepends a leading dimension to the same compiled
        arithmetic — verified in ``tests/test_batch_dispatch.py``.

        With ``use_pallas`` the solve instead routes through
        ``make_solver(batch=True)`` so the whole batch advances via the
        fused batched Pallas kernel (one launch per step, donated state
        buffers); kernel-vs-oracle accuracy is tolerance-level there, so
        the bit-identity guarantee applies to the default (pure-XLA) path.
        """
        from .solver import AOTBatchCache

        if self.use_pallas:
            solver = make_solver(
                self.cfg, self.bathymetry(), self.probe_indices(),
                use_pallas=True, batch=True,
            )
            n_steps, dt = solver.n_steps, solver.dt
            thr = self.arrival_threshold
            t_norm = n_steps * dt

            def forward(thetas: jax.Array) -> jax.Array:
                thetas = jnp.atleast_2d(thetas)
                eta0 = jax.vmap(self.displacement)(thetas)
                series, _ = solver(eta0)  # (B, n_steps, n_probes)
                hmax = jnp.max(series, axis=1)
                k = 40.0 / thr
                crossed = jax.nn.sigmoid(k * (series - thr))
                not_yet = jnp.cumprod(1.0 - crossed, axis=1)
                t_arr = jnp.sum(not_yet, axis=1) * dt / t_norm
                return jnp.stack(
                    [hmax[:, 0], t_arr[:, 0], hmax[:, 1], t_arr[:, 1]],
                    axis=-1,
                )

            forward.n_steps = n_steps
            forward.dt = dt
            forward.executables = solver.executables
            return forward

        single = self.build_forward()
        # No donate: a (B, 2) theta buffer cannot alias any output (the
        # solver-level factory donates the (B, ny, nx) state buffers,
        # where aliasing is real).  Padding repeats member 0 — any valid
        # theta works; zeros would too, but stay inside the prior box.
        cache = AOTBatchCache(
            jax.vmap(single), key=(self.ny, self.nx),
            dtype=jnp.result_type(float), pad="repeat",
            name=f"swe_forward_{self.ny}x{self.nx}",
        )

        def forward(thetas: jax.Array) -> jax.Array:
            out, n = cache(jnp.atleast_2d(thetas))
            return out[:n]

        forward.n_steps = single.n_steps
        forward.dt = single.dt
        forward.executables = cache.executables
        return forward

    def build_stacked_forward(self) -> Callable:
        """Traceable thetas ``(B, 2)`` -> observables ``(B, 4)``.

        The raw ``jax.vmap`` of the single forward, with NO jit/AOT/padding
        wrapper — :class:`repro.balancer.types.ShardedBatchServer` needs a
        traceable stacked callable it can ``shard_map`` over the device
        mesh and AOT-compile itself (``build_batch_forward`` returns an
        already-compiled Python callable, which cannot be re-traced).
        """
        single = self.build_forward()
        vmapped = jax.vmap(single)

        def forward(thetas: jax.Array) -> jax.Array:
            return vmapped(thetas)

        forward.n_steps = single.n_steps
        forward.dt = single.dt
        return forward

    def build_series_forward(self) -> Callable:
        """theta -> full probe-0 SSHA time series (for the Fig. 6 GP)."""
        solver = make_solver(
            self.cfg, self.bathymetry(), self.probe_indices(), use_pallas=self.use_pallas
        )

        def forward(theta: jax.Array) -> jax.Array:
            series, _ = solver(self.displacement(theta))
            return series[:, 0]

        forward.n_steps = solver.n_steps
        forward.dt = solver.dt
        return forward


# ---------------------------------------------------------------------------
# Inverse problem assembly (paper §4)
# ---------------------------------------------------------------------------
@dataclass
class TohokuInverseProblem:
    """Uniform prior (Fig. 4) + Gaussian likelihood on (height, arrival)."""

    scenario_fine: TohokuScenario
    noise_height: float = 0.04  # [m] probe noise + model discrepancy
    noise_arrival: float = 0.012  # normalised-time units
    theta_true: Tuple[float, float] = (0.0, 0.0)
    obs_seed: int = 1234
    y_obs: Optional[np.ndarray] = None

    def prior_bounds(self) -> Tuple[np.ndarray, np.ndarray]:
        lo = np.array([PRIOR_X[0], PRIOR_Y[0]])
        hi = np.array([PRIOR_X[1], PRIOR_Y[1]])
        return lo, hi

    def log_prior(self, theta) -> float:
        lo, hi = self.prior_bounds()
        t = np.asarray(theta)
        if np.any(t < lo) or np.any(t > hi):
            return float("-inf")
        return -float(np.sum(np.log(hi - lo)))

    def log_prior_jax(self, theta: jax.Array) -> jax.Array:
        lo, hi = self.prior_bounds()
        inside = jnp.all((theta >= lo) & (theta <= hi))
        return jnp.where(inside, -jnp.sum(jnp.log(jnp.asarray(hi - lo))), -jnp.inf)

    def sample_prior(self, rng: np.random.Generator, n: int = 1) -> np.ndarray:
        lo, hi = self.prior_bounds()
        return rng.uniform(lo, hi, size=(n, 2))

    def noise_sigma(self) -> np.ndarray:
        return np.array(
            [self.noise_height, self.noise_arrival, self.noise_height, self.noise_arrival]
        )

    def generate_observations(self, forward_fine: Callable) -> np.ndarray:
        """Synthetic y: fine model at theta_true + measurement noise."""
        if self.y_obs is None:
            rng = np.random.default_rng(self.obs_seed)
            clean = np.asarray(forward_fine(jnp.asarray(self.theta_true)))
            self.y_obs = clean + rng.normal(size=clean.shape) * self.noise_sigma()
        return self.y_obs

    def log_likelihood(self, obs) -> float:
        assert self.y_obs is not None, "call generate_observations first"
        r = (np.asarray(obs) - self.y_obs) / self.noise_sigma()
        return -0.5 * float(np.sum(r * r))

    def log_likelihood_jax(self, obs: jax.Array) -> jax.Array:
        assert self.y_obs is not None, "call generate_observations first"
        r = (obs - jnp.asarray(self.y_obs)) / jnp.asarray(self.noise_sigma())
        return -0.5 * jnp.sum(r * r)


def make_hierarchy(
    *,
    fine: TohokuScenario,
    coarse: TohokuScenario,
    problem: Optional[TohokuInverseProblem] = None,
) -> Dict[str, object]:
    """Assemble the paper's three-level setup: GP / coarse PDE / fine PDE.

    Returns forwards + the inverse problem; GP training happens in
    :func:`train_level0_gp` because it needs level-1 solves (paper §6.1).
    """
    problem = problem or TohokuInverseProblem(scenario_fine=fine)
    f_fine = jax.jit(fine.build_forward())
    f_coarse = jax.jit(coarse.build_forward())
    problem.generate_observations(f_fine)
    return {
        "problem": problem,
        "forward_fine": f_fine,
        "forward_coarse": f_coarse,
        # Stacked (B, 2) -> (B, 4) handlers for BatchServer pools (the AOT
        # executables compile lazily, per realised batch size).
        "forward_fine_batch": fine.build_batch_forward(),
        "forward_coarse_batch": coarse.build_batch_forward(),
    }


def train_level0_gp(
    forward_coarse: Callable,
    problem: TohokuInverseProblem,
    *,
    n_train: int = 512,
    seed: int = 0,
    steps: int = 200,
):
    """Paper §6.1: GP on 512 LHS draws of the level-1 (coarse) model."""
    from repro.core.gp import fit_gp
    from repro.core.lhs import latin_hypercube, scale_to_bounds

    lo, hi = problem.prior_bounds()
    u = latin_hypercube(jax.random.key(seed), n_train, 2)
    x = scale_to_bounds(u, lo, hi)
    ys = jax.lax.map(forward_coarse, x, batch_size=16)
    return fit_gp(x, ys, steps=steps)
