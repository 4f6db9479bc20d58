"""Gaussian-process surrogate (paper §6.1, level 0 of the MLDA hierarchy).

Matches the paper's configuration: Matérn-5/2 kernel, zero mean, automatic
relevance determination (one lengthscale per input dimension), hyperparameters
optimised by maximising the marginal likelihood on the training data; trained
on Latin-hypercube samples of the level-1 model.  The paper's GP is PyTorch;
ours is JAX (DESIGN.md §7.5).

Supports vector-valued outputs (independent outputs sharing one kernel) —
used both for the (height, arrival-time) observables and for the full
time-series reconstruction of Fig. 6.

The O(n^2 d) kernel-matrix assembly is the compute hot-spot; a Pallas TPU
kernel lives in ``repro.kernels.matern`` (used when ``use_pallas=True``),
with this module's pure-jnp path as the reference implementation.

The posterior mean, which level 0 of the MLDA hierarchy serves, is one
compiled program per batch size (:func:`posterior_mean`): the trained
state is passed as jit arguments, so every GP fitted with the same shapes
shares those programs.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

SQRT5 = math.sqrt(5.0)


class GPParams(NamedTuple):
    log_lengthscales: jax.Array  # (d,) ARD
    log_outputscale: jax.Array  # ()
    log_noise: jax.Array  # ()


def _matern52_of_sq_dist(d2: jax.Array, params: GPParams) -> jax.Array:
    """Matérn-5/2 of squared lengthscale-scaled distances."""
    # The double-where keeps the gradient of sqrt finite at d2 == 0 (the
    # diagonal), else ML-II training NaNs out.
    d2 = jnp.maximum(d2, 0.0)
    safe = jnp.where(d2 > 1e-24, d2, 1.0)
    d = jnp.where(d2 > 1e-24, jnp.sqrt(safe), 0.0)
    s = SQRT5 * d
    return jnp.exp(params.log_outputscale) * (1.0 + s + s * s / 3.0) * jnp.exp(-s)


def matern52(x1: jax.Array, x2: jax.Array, params: GPParams) -> jax.Array:
    """Matérn-5/2 ARD kernel matrix k(x1, x2): (n, d) x (m, d) -> (n, m)."""
    ls = jnp.exp(params.log_lengthscales)
    a = x1 / ls
    b = x2 / ls
    # Pairwise Euclidean distances.  HIGHEST: a TPU runs fp32 matmuls as
    # one bf16 pass by default, and |a|^2 + |b|^2 - 2ab cancels — 3e-2
    # error in k on a v5e without it.
    ab = jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
    d2 = jnp.sum(a * a, -1)[:, None] + jnp.sum(b * b, -1)[None, :] - 2.0 * ab
    return _matern52_of_sq_dist(d2, params)


def _dot_last(a: jax.Array, b: jax.Array) -> jax.Array:
    """``sum_k a[..., k] * b[..., k]``, added in the order k = 0, 1, ...

    The order is fixed by the code, not picked by the compiler per shape
    as it is for a reduce or a matmul.
    """
    out = a[..., 0] * b[..., 0]
    for k in range(1, a.shape[-1]):
        out = out + a[..., k] * b[..., k]
    return out


def _matern52_rows(x1: jax.Array, x2: jax.Array, params: GPParams) -> jax.Array:
    """:func:`matern52` with each row's arithmetic independent of how many
    rows ``x1`` has.

    The distances are float32 products and sums on the vector unit, not a
    matmul: a v5e runs the ``HIGHEST`` matmul on the MXU for two rows or
    more but rewrites it into this float32 form for one, so the rows of
    the two differ by ulps.
    """
    ls = jnp.exp(params.log_lengthscales)
    a = x1 / ls
    b = x2 / ls
    ab = _dot_last(a[:, None, :], b[None, :, :])
    d2 = _dot_last(a, a)[:, None] + _dot_last(b, b)[None, :] - 2.0 * ab
    return _matern52_of_sq_dist(d2, params)


def _kernel_fn(use_pallas: bool) -> Callable:
    if use_pallas:
        from repro.kernels.matern import ops as matern_ops

        return matern_ops.matern52
    return matern52


def _tree_sum(t: jax.Array) -> jax.Array:
    """Sum of (m, n, p) over n as a fixed binary tree of elementwise adds.

    n is zero-padded to a power of two; adding a zero is exact.
    """
    n = t.shape[1]
    t = jnp.pad(t, ((0, 0), (0, (1 << (n - 1).bit_length()) - n), (0, 0)))
    while t.shape[1] > 1:
        half = t.shape[1] // 2
        t = t[:, :half] + t[:, half:]
    return t[:, 0]


@partial(jax.jit, static_argnames=("use_pallas",))
def posterior_mean(
    x: jax.Array,
    x_train: jax.Array,
    alpha: jax.Array,
    y_scale: jax.Array,
    y_mean: jax.Array,
    params: GPParams,
    *,
    use_pallas: bool = False,
) -> jax.Array:
    """GP posterior mean at ``x``: (m, d) -> (m, p), one XLA program per m.

    The trained state is an argument, never a closed-over constant: a
    freshly fitted GP of the same shapes reuses the compiled programs
    (and the persistent compile cache) instead of compiling new ones.

    Row ``i`` of the result is bit-identical for every ``m`` (the
    coalesced-dispatch guarantee; checked for the jnp kernel, not the
    Pallas one): the kernel row comes from :func:`_matern52_rows`, and
    ``ks @ alpha`` is an elementwise multiply and a fixed-order tree of
    adds over n.  A GEMM there picks its blocking per m on a CPU, and on a
    v5e a reduce over n takes its order from m's layout; the ulp either
    costs is amplified by the cancelling sum.
    """
    x = jnp.atleast_2d(x)
    if use_pallas:
        ks = _kernel_fn(True)(x, x_train, params)  # (m, n)
    else:
        ks = _matern52_rows(x, x_train, params)
    return _tree_sum(ks[:, :, None] * alpha[None, :, :]) * y_scale + y_mean


NOISE_FLOOR = 1e-5  # keeps fp32 Cholesky well-conditioned on normalised y


def neg_log_marginal_likelihood(
    params: GPParams, x: jax.Array, y: jax.Array, jitter: float = 1e-5
) -> jax.Array:
    """-log p(y | x, params); y may be (n,) or (n, p) (independent outputs)."""
    n = x.shape[0]
    y2 = y if y.ndim == 2 else y[:, None]
    noise = NOISE_FLOOR + jnp.exp(params.log_noise)
    k = matern52(x, x, params) + (noise + jitter) * jnp.eye(n)
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y2)
    p = y2.shape[1]
    quad = jnp.sum(y2 * alpha)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol)))
    return 0.5 * quad + 0.5 * p * logdet + 0.5 * n * p * math.log(2.0 * math.pi)


@dataclass
class GaussianProcess:
    """Trained GP surrogate; construct via :func:`fit_gp`."""

    x_train: jax.Array  # (n, d)
    y_train: jax.Array  # (n, p)
    y_mean: jax.Array  # (p,) — outputs are centred (zero-mean GP, as in paper)
    y_scale: jax.Array  # (p,)
    params: GPParams
    chol: jax.Array  # (n, n)
    alpha: jax.Array  # (n, p)
    use_pallas: bool = False

    def predict(self, x: jax.Array, return_var: bool = False):
        """Posterior mean (and variance) at x: (m, d) -> (m, p).

        The mean is one compiled program per m (:func:`posterior_mean`);
        the variance, which no served level asks for, evaluates eagerly.
        """
        mean = posterior_mean(
            x, self.x_train, self.alpha, self.y_scale, self.y_mean, self.params,
            use_pallas=self.use_pallas,
        )
        if not return_var:
            return mean
        x = jnp.atleast_2d(x)
        ks = _kernel_fn(self.use_pallas)(x, self.x_train, self.params)  # (m, n)
        v = jax.scipy.linalg.solve_triangular(self.chol, ks.T, lower=True)
        kss = jnp.exp(self.params.log_outputscale)
        var = jnp.maximum(kss - jnp.sum(v * v, axis=0), 1e-12)
        return mean, var[:, None] * self.y_scale**2

    def __call__(self, theta: jax.Array) -> jax.Array:
        """UM-Bridge model interface: single-point evaluation."""
        return self.predict(jnp.atleast_2d(theta))[0]

    def batch_call(self, thetas: jax.Array) -> jax.Array:
        """Batched posterior mean for a stacked ``(B, d)`` parameter array.

        One launch of the compiled :func:`posterior_mean` program for this
        ``B`` — a ``(B, n)`` kernel assembly + one fixed-order contraction
        (deliberately NOT a GEMM) — answers the whole coalesced batch: the
        :class:`repro.balancer.types.BatchServer` handler for level 0.
        Row ``i`` runs the same arithmetic as ``__call__(thetas[i])``
        regardless of ``B``, so members are bit-identical (fp32) to
        per-request evaluation — verified in
        ``tests/test_batch_dispatch.py`` on the CPU, and on a v5e.
        """
        return self.predict(thetas)


def fit_gp(
    x: jax.Array,
    y: jax.Array,
    *,
    steps: int = 200,
    lr: float = 0.05,
    jitter: float = 1e-5,
    init_noise: float = 1e-2,
    use_pallas: bool = False,
    seed: int = 0,
) -> GaussianProcess:
    """ML-II hyperparameter optimisation by Adam on the marginal likelihood.

    The paper optimises the marginal likelihood of a PyTorch GP; we run Adam
    on (log-lengthscales, log-outputscale, log-noise) in JAX.  The O(n^3)
    Cholesky at n=512 is negligible relative to PDE solves (paper §6.1).
    """
    x = jnp.asarray(x, jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
    y = jnp.asarray(y)
    y2 = y if y.ndim == 2 else y[:, None]
    y_mean = jnp.mean(y2, axis=0)
    y_scale = jnp.maximum(jnp.std(y2, axis=0), 1e-12)
    y_n = (y2 - y_mean) / y_scale

    # Median-heuristic lengthscale init.
    med = jnp.maximum(jnp.median(jnp.abs(x - jnp.median(x, axis=0)), axis=0), 1e-3)
    params = GPParams(
        log_lengthscales=jnp.log(med * 2.0),
        log_outputscale=jnp.zeros(()),
        log_noise=jnp.log(jnp.asarray(init_noise)),
    )

    loss_fn = partial(neg_log_marginal_likelihood, x=x, y=y_n, jitter=jitter)

    # Minimal Adam (repro.optim is for the LM stack; keep core self-contained).
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    b1, b2, eps = 0.9, 0.999, 1e-8

    @jax.jit
    def step(carry, _):
        params, m, v, t = carry
        loss, g = jax.value_and_grad(loss_fn)(params)
        # Clip the global gradient norm — ML-II objectives have cliffs when
        # the kernel matrix approaches singularity.
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, 10.0 / (gnorm + 1e-12))
        g = jax.tree.map(lambda x: x * scale, g)
        t = t + 1
        m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        mhat = jax.tree.map(lambda a: a / (1 - b1**t), m)
        vhat = jax.tree.map(lambda a: a / (1 - b2**t), v)
        new_params = jax.tree.map(
            lambda p, a, b: p - lr * a / (jnp.sqrt(b) + eps), params, mhat, vhat
        )
        # Reject non-finite steps (failed Cholesky) and keep previous params.
        ok = jnp.isfinite(loss) & jnp.all(
            jnp.asarray([jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(new_params)])
        )
        params = jax.tree.map(lambda a, b: jnp.where(ok, a, b), new_params, params)
        return (params, m, v, t), loss

    (params, _, _, _), losses = jax.lax.scan(
        step, (params, m, v, jnp.zeros((), jnp.int32)), None, length=steps
    )

    n = x.shape[0]
    noise = NOISE_FLOOR + jnp.exp(params.log_noise)
    # Adaptive jitter ladder: ML-II on noiseless smooth data drives the
    # kernel matrix towards singularity; find the smallest jitter that
    # factorises cleanly in fp32 (standard GPML practice).
    chol = None
    for j in (jitter, 1e-4, 1e-3, 1e-2, 1e-1):
        k = matern52(x, x, params) + (noise + j) * jnp.eye(n)
        c = jnp.linalg.cholesky(k)
        if bool(jnp.all(jnp.isfinite(c))):
            chol = c
            break
    if chol is None:
        raise FloatingPointError("GP kernel matrix could not be factorised")
    alpha = jax.scipy.linalg.cho_solve((chol, True), y_n)
    return GaussianProcess(
        x_train=x,
        y_train=y2,
        y_mean=y_mean,
        y_scale=y_scale,
        params=params,
        chol=chol,
        alpha=alpha,
        use_pallas=use_pallas,
    )
