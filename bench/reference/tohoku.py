"""Plain reference of the three-level Tōhoku inversion's forward outputs.

* Fine and coarse levels: a first-order finite-volume shallow-water solve
  (hydrostatic reconstruction of Audusse et al. 2004, Rusanov flux,
  desingularised velocities, forward Euler at a fixed CFL step) of a
  Gaussian sea-surface bump centred at ``theta`` (km) over a synthetic
  trench bathymetry, observed at two probes as (maximum height, soft
  arrival time) each.
* Level 0: a Gaussian process with a Matérn-5/2 ARD kernel, trained by
  ML-II (Adam on the marginal likelihood) on a Latin hypercube of the
  coarse level, as the paper's §6.1 states; its posterior mean.

Everything is written from the configuration file's ``scenario`` and
``gp`` groups.  Two choices keep float32 sound at ocean depth, and any
implementation of the scheme needs them: the momentum flux carries no
``g h^2 / 2`` term, and the pressure and bed-slope terms are assembled per
cell as ``(h_a - h_b)(h_a + h_b)`` of the reconstructed depths, so that a
7 km column does not cancel ``2.4e8``-sized numbers.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SQRT5 = math.sqrt(5.0)


# ---------------------------------------------------------------------------
# scenario geometry
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Grid:
    nx: int
    ny: int
    sc: Any  # the configuration's "scenario" dict (hashable view below)

    @property
    def dx(self) -> float:
        x0, x1 = self.sc["domain_x_km"]
        return (x1 - x0) * 1000.0 / self.nx

    @property
    def dy(self) -> float:
        y0, y1 = self.sc["domain_y_km"]
        return (y1 - y0) * 1000.0 / self.ny

    def centres_km(self) -> Tuple[np.ndarray, np.ndarray]:
        x0, x1 = self.sc["domain_x_km"]
        y0, y1 = self.sc["domain_y_km"]
        xe = np.linspace(x0, x1, self.nx + 1, dtype=np.float32)
        ye = np.linspace(y0, y1, self.ny + 1, dtype=np.float32)
        return 0.5 * (xe[:-1] + xe[1:]), 0.5 * (ye[:-1] + ye[1:])

    def bed(self) -> np.ndarray:
        """Bed elevation (m, negative under water), ``(ny, nx)``: a 7 km
        plain, a shelf rising to the west, a trench, a seamount ridge and
        a dry strip at the western edge."""
        xc, yc = self.centres_km()
        X, Y = np.meshgrid(xc.astype(np.float64), yc.astype(np.float64))
        x_west = self.sc["domain_x_km"][0]
        b = (
            -7000.0
            + 6950.0 * np.exp(-(((X - x_west) / 220.0) ** 2))
            - 1500.0 * np.exp(-(((X - 120.0) / 90.0) ** 2))
            + 800.0 * np.exp(-(((X - 700.0) / 260.0) ** 2 + ((Y - 250.0) / 330.0) ** 2))
        )
        b = np.where(X < x_west + 40.0, 50.0, b)
        return b.astype(np.float32)

    def probes(self) -> Tuple[np.ndarray, np.ndarray]:
        xc, yc = self.centres_km()
        rows = [int(np.argmin(np.abs(yc - py))) for _, py in self.sc["probes_km"]]
        cols = [int(np.argmin(np.abs(xc - px))) for px, _ in self.sc["probes_km"]]
        return np.asarray(rows), np.asarray(cols)

    def time_step(self) -> Tuple[float, int]:
        """The fixed CFL step from the deepest water, and the step count."""
        sc = self.sc
        h_max = float(np.max(np.maximum(-self.bed(), 0.0)))
        c = math.sqrt(sc["g"] * max(h_max, 1.0)) + sc["speed_margin_m_s"]
        dt = sc["cfl"] * min(self.dx, self.dy) / c
        return dt, int(math.ceil(sc["t_end_s"] / dt))


# ---------------------------------------------------------------------------
# the finite-volume scheme
# ---------------------------------------------------------------------------
def _velocity(h, hq, eps):
    """hq / h, desingularised where the cell is (nearly) dry."""
    h4 = h**4
    return math.sqrt(2.0) * h * hq / jnp.sqrt(h4 + jnp.maximum(h4, eps**4))


def _tendency(h, qn, qt, b, spacing, g, eps, axis):
    """Flux divergence plus well-balanced source along ``axis`` for depth
    ``h``, normal momentum ``qn`` and tangential momentum ``qt``."""

    def faces(q):
        # Zero-gradient ghost cells; face i lies between cells i-1 and i.
        lo = jnp.concatenate([jnp.take(q, jnp.array([0]), axis=axis), q], axis=axis)
        hi = jnp.concatenate([q, jnp.take(q, jnp.array([-1]), axis=axis)], axis=axis)
        return lo, hi

    (hl, hr), (ql, qr), (tl, tr), (bl, br) = faces(h), faces(qn), faces(qt), faces(b)
    b_face = jnp.maximum(bl, br)
    h_l = jnp.maximum(hl + bl - b_face, 0.0)  # reconstructed depth left of the face
    h_r = jnp.maximum(hr + br - b_face, 0.0)
    un_l, un_r = _velocity(hl, ql, eps), _velocity(hr, qr, eps)
    ut_l, ut_r = _velocity(hl, tl, eps), _velocity(hr, tr, eps)

    def wave_speed(hh, u):
        return jnp.abs(u) + jnp.where(hh > 0, jnp.sqrt(g * jnp.where(hh > 0, hh, 1.0)), 0.0)

    a = jnp.maximum(wave_speed(h_l, un_l), wave_speed(h_r, un_r))
    m_l, m_r = h_l * un_l, h_r * un_r  # normal mass fluxes
    f_h = 0.5 * (m_l + m_r) - 0.5 * a * (h_r - h_l)
    f_n = 0.5 * (m_l * un_l + m_r * un_r) - 0.5 * a * (m_r - m_l)
    f_t = 0.5 * (h_l * ut_l * un_l + h_r * ut_r * un_r) - 0.5 * a * (h_r * ut_r - h_l * ut_l)

    n = h.shape[axis]

    def right(q):  # the face on the cell's right / top
        return jax.lax.slice_in_dim(q, 1, n + 1, axis=axis)

    def left(q):
        return jax.lax.slice_in_dim(q, 0, n, axis=axis)

    # Pressure + bed slope: g/2 [(h_r*^2 - h_l*^2) at both faces of the cell].
    press = 0.25 * g * (
        (right(h_r) - right(h_l)) * (right(h_r) + right(h_l))
        + (left(h_r) - left(h_l)) * (left(h_r) + left(h_l))
    )
    d_h = (right(f_h) - left(f_h)) / spacing
    d_n = (right(f_n) - left(f_n) + press) / spacing
    d_t = (right(f_t) - left(f_t)) / spacing
    return d_h, d_n, d_t


def swe_step(state, b, dx, dy, dt, g, eps):
    h, hu, hv = state
    ax_h, ax_u, ax_v = _tendency(h, hu, hv, b, dx, g, eps, axis=1)
    ay_h, ay_v, ay_u = _tendency(h, hv, hu, b, dy, g, eps, axis=0)
    h1 = jnp.maximum(h - dt * (ax_h + ay_h), 0.0)
    wet = h1 > eps
    hu1 = jnp.where(wet, hu - dt * (ax_u + ay_u), 0.0)
    hv1 = jnp.where(wet, hv - dt * (ax_v + ay_v), 0.0)
    return h1, hu1, hv1


def observe(series, threshold: float):
    """(T, P) probe heights -> [hmax_1, t_arr_1, hmax_2, t_arr_2]: the
    maximum, and the share of the window before the height first crosses
    ``threshold`` (a sigmoid of slope 40/threshold makes it smooth)."""
    k = 40.0 / threshold
    crossed = jax.nn.sigmoid(k * (series - threshold))
    t_arr = jnp.sum(jnp.cumprod(1.0 - crossed, axis=0), axis=0) / series.shape[0]
    hmax = jnp.max(series, axis=0)
    return jnp.stack([hmax[0], t_arr[0], hmax[1], t_arr[1]])


class Forward:
    """theta (k, 2) km -> observables (k, 4) at one grid, in ``dtype``."""

    def __init__(self, scenario: Dict[str, Any], grid, dtype=jnp.float32, block: int = 64):
        self.sc = dict(scenario)
        self.grid = Grid(int(grid[0]), int(grid[1]), self.sc)  # (nx, ny)
        self.dtype = dtype
        self.block = int(block)
        self.dt, self.n_steps = self.grid.time_step()
        self._fn = jax.jit(jax.vmap(self._one))

    def _one(self, theta):
        sc, gd, dt_ = self.sc, self.grid, self.dtype
        b = jnp.asarray(gd.bed(), dt_)
        xc, yc = gd.centres_km()
        X, Y = jnp.meshgrid(jnp.asarray(xc), jnp.asarray(yc))
        r2 = ((X - theta[0]) ** 2 + (Y - theta[1]) ** 2) / sc["bump_sigma_km"] ** 2
        eta0 = (sc["bump_height_m"] * jnp.exp(-0.5 * r2)).astype(dt_)
        eps = sc["dry_depth_m"]
        h_rest = jnp.maximum(-b, 0.0)
        h0 = jnp.where(h_rest > eps, jnp.maximum(h_rest + eta0, 0.0), h_rest)
        zero = jnp.zeros_like(h0)
        rows, cols = gd.probes()
        dt = jnp.asarray(self.dt, dt_)
        g = jnp.asarray(sc["g"], dt_)

        def body(state, _):
            state = swe_step(state, b, gd.dx, gd.dy, dt, g, eps)
            return state, (state[0] + b)[rows, cols]

        _, series = jax.lax.scan(body, (h0, zero, zero), None, length=self.n_steps)
        return observe(series.astype(jnp.float32), sc["arrival_threshold_m"])

    def __call__(self, thetas) -> np.ndarray:
        thetas = np.asarray(thetas, np.float32).reshape(-1, 2)
        out = []
        with jax.default_matmul_precision("highest"):
            for i in range(0, len(thetas), self.block):
                chunk = thetas[i : i + self.block]
                pad = self.block - len(chunk)
                padded = np.concatenate([chunk, np.repeat(chunk[:1], pad, 0)]) if pad else chunk
                out.append(np.asarray(self._fn(jnp.asarray(padded)))[: len(chunk)])
        return np.concatenate(out).astype(np.float64)


# ---------------------------------------------------------------------------
# the observations and the coarse log-posterior
# ---------------------------------------------------------------------------
def noise_sigma(scenario) -> np.ndarray:
    s = scenario
    return np.array([s["noise_height_m"], s["noise_arrival"], s["noise_height_m"], s["noise_arrival"]])


def observations(scenario, fine: Forward) -> np.ndarray:
    """The synthetic data: the fine level at the true source plus seeded
    Gaussian noise."""
    clean = fine(np.asarray([scenario["theta_true_km"]], np.float32))[0]
    rng = np.random.default_rng(scenario["obs_seed"])
    return clean + rng.normal(size=clean.shape) * noise_sigma(scenario)


def log_posterior(scenario, obs: np.ndarray, y: np.ndarray, thetas: np.ndarray) -> np.ndarray:
    """Uniform prior on the box plus the Gaussian likelihood, per row."""
    lo = np.asarray(scenario["prior_lo_km"], np.float64)
    hi = np.asarray(scenario["prior_hi_km"], np.float64)
    t = np.asarray(thetas, np.float64)
    inside = np.all((t >= lo) & (t <= hi), axis=-1)
    r = (np.asarray(obs, np.float64) - y) / noise_sigma(scenario)
    lp = -np.sum(np.log(hi - lo)) - 0.5 * np.sum(r * r, axis=-1)
    return np.where(inside, lp, -np.inf)


# ---------------------------------------------------------------------------
# level 0: the Gaussian-process surrogate
# ---------------------------------------------------------------------------
def latin_hypercube(key, n: int, d: int):
    """n points in [0, 1)^d, one in each of n strata per dimension."""
    k_perm, k_jit = jax.random.split(key)
    perms = jnp.stack([jax.random.permutation(k, n) for k in jax.random.split(k_perm, d)], axis=1)
    return (perms + jax.random.uniform(k_jit, (n, d))) / n


def matern52(x1, x2, log_ls, log_os, dtype=jnp.float32):
    """Matérn-5/2 ARD covariance from explicit coordinate differences."""
    ls = jnp.exp(log_ls)
    diff = (x1[:, None, :] / ls - x2[None, :, :] / ls).astype(dtype)
    r2 = jnp.sum(diff * diff, axis=-1)
    # sqrt has no derivative at 0 (the diagonal): keep ML-II's gradient finite.
    r = jnp.where(r2 > 1e-24, jnp.sqrt(jnp.where(r2 > 1e-24, r2, 1.0)), 0.0)
    s = SQRT5 * r
    return (jnp.exp(log_os) * (1.0 + s + s * s / 3.0) * jnp.exp(-s)).astype(jnp.float32)


def _nlml(params, x, y, noise_floor, jitter):
    log_ls, log_os, log_noise = params
    n, p = y.shape
    k = matern52(x, x, log_ls, log_os) + (noise_floor + jnp.exp(log_noise) + jitter) * jnp.eye(n)
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol)))
    return 0.5 * jnp.sum(y * alpha) + 0.5 * p * logdet + 0.5 * n * p * math.log(2.0 * math.pi)


@partial(jax.jit, static_argnames=("steps", "lr", "clip", "noise_floor", "jitter"))
def _adam(params, x, y, *, steps, lr, clip, noise_floor, jitter):
    grad = jax.value_and_grad(_nlml)
    b1, b2, eps = 0.9, 0.999, 1e-8
    zeros = jax.tree.map(jnp.zeros_like, params)

    def step(carry, _):
        p, m, v, t = carry
        loss, g = grad(p, x, y, noise_floor, jitter)
        norm = jnp.sqrt(sum(jnp.sum(gi * gi) for gi in g))
        g = jax.tree.map(lambda gi: gi * jnp.minimum(1.0, clip / (norm + 1e-12)), g)
        t = t + 1
        m = jax.tree.map(lambda a, gi: b1 * a + (1 - b1) * gi, m, g)
        v = jax.tree.map(lambda a, gi: b2 * a + (1 - b2) * gi * gi, v, g)
        new = jax.tree.map(
            lambda pi, a, c: pi - lr * (a / (1 - b1**t)) / (jnp.sqrt(c / (1 - b2**t)) + eps),
            p, m, v,
        )
        ok = jnp.isfinite(loss) & jnp.all(jnp.stack([jnp.all(jnp.isfinite(q)) for q in new]))
        p = jax.tree.map(lambda a, b: jnp.where(ok, a, b), new, p)
        return (p, m, v, t), loss

    (params, _, _, _), _ = jax.lax.scan(
        step, (params, zeros, zeros, jnp.zeros((), jnp.int32)), None, length=steps
    )
    return params


class GaussianProcess:
    """ML-II GP on the coarse level at a Latin hypercube of the prior box."""

    def __init__(self, scenario: Dict[str, Any], gp: Dict[str, Any], coarse: Forward):
        lo = np.asarray(scenario["prior_lo_km"], np.float32)
        hi = np.asarray(scenario["prior_hi_km"], np.float32)
        with jax.default_matmul_precision("highest"):
            u = latin_hypercube(jax.random.key(gp["lhs_seed"]), gp["train_points"], lo.size)
            x = jnp.asarray(lo) + u * jnp.asarray(hi - lo)
            y = jnp.asarray(coarse(np.asarray(x)), jnp.float32)
            self.y_mean = jnp.mean(y, axis=0)
            self.y_scale = jnp.maximum(jnp.std(y, axis=0), 1e-12)
            y_n = (y - self.y_mean) / self.y_scale
            spread = jnp.median(jnp.abs(x - jnp.median(x, axis=0)), axis=0)
            params = (
                jnp.log(2.0 * jnp.maximum(spread, 1e-3)),
                jnp.zeros(()),
                jnp.log(jnp.asarray(gp["init_noise"], jnp.float32)),
            )
            params = _adam(
                params, x, y_n, steps=int(gp["adam_steps"]), lr=float(gp["adam_lr"]),
                clip=float(gp["grad_clip"]), noise_floor=float(gp["noise_floor"]),
                jitter=float(gp["jitter"]),
            )
            noise = gp["noise_floor"] + jnp.exp(params[2])
            for j in gp["jitter_ladder"]:
                k = matern52(x, x, params[0], params[1]) + (noise + j) * jnp.eye(x.shape[0])
                chol = jnp.linalg.cholesky(k)
                if bool(jnp.all(jnp.isfinite(chol))):
                    break
            else:
                raise FloatingPointError("reference GP: no jitter factorises the kernel")
            self.alpha = jax.scipy.linalg.cho_solve((chol, True), y_n)
        self.x, self.params = x, params

    def __call__(self, thetas, dtype=jnp.float32) -> np.ndarray:
        """Posterior mean at ``thetas`` (k, 2) -> (k, 4); ``dtype`` is the
        precision of the cross-covariances (bfloat16 for the control)."""
        with jax.default_matmul_precision("highest"):
            ks = matern52(jnp.asarray(thetas, jnp.float32), self.x, *self.params[:2], dtype=dtype)
            mean = ks @ self.alpha * self.y_scale + self.y_mean
        return np.asarray(mean, np.float64)
