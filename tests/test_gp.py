"""GP surrogate (paper §6.1 configuration) tests."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fit_gp, latin_hypercube, scale_to_bounds
from repro.core.gp import GPParams, matern52, posterior_mean


def _func(x):
    return jnp.sin(3 * x[:, 0]) * jnp.cos(2 * x[:, 1])


def test_fit_accuracy_smooth_function():
    x = latin_hypercube(jax.random.key(0), 128, 2)
    gp = fit_gp(x, _func(x), steps=150)
    xt = latin_hypercube(jax.random.key(1), 64, 2)
    rmse = float(jnp.sqrt(jnp.mean((gp.predict(xt)[:, 0] - _func(xt)) ** 2)))
    assert rmse < 0.02


def test_vector_output_gp():
    x = latin_hypercube(jax.random.key(0), 96, 2)
    y = jnp.stack([_func(x), jnp.cos(4 * x[:, 0])], axis=1)
    gp = fit_gp(x, y, steps=120)
    pred = gp.predict(x[:8])
    assert pred.shape == (8, 2)
    assert float(jnp.max(jnp.abs(pred - y[:8]))) < 0.05


def test_variance_small_at_train_large_far_away():
    x = latin_hypercube(jax.random.key(0), 64, 2) * 0.5  # cluster in a corner
    gp = fit_gp(x, _func(x), steps=100)
    _, var_train = gp.predict(x[:8], return_var=True)
    _, var_far = gp.predict(jnp.ones((1, 2)) * 5.0, return_var=True)
    assert float(var_train.mean()) < float(var_far.mean())


def test_ard_discovers_irrelevant_dimension():
    key = jax.random.key(2)
    x = latin_hypercube(key, 160, 3)
    y = jnp.sin(4 * x[:, 0]) + 0.5 * x[:, 1]  # dim 2 irrelevant
    gp = fit_gp(x, y, steps=250)
    ls = np.exp(np.asarray(gp.params.log_lengthscales))
    assert ls[2] > 1.5 * ls[0], f"ARD failed: {ls}"


def test_latin_hypercube_stratification():
    n, d = 64, 3
    u = np.asarray(latin_hypercube(jax.random.key(0), n, d))
    assert u.shape == (n, d)
    for j in range(d):
        counts, _ = np.histogram(u[:, j], bins=n, range=(0, 1))
        assert np.all(counts == 1), "one sample per stratum violated"


def test_scale_to_bounds():
    u = jnp.array([[0.0, 0.5], [1.0, 0.25]])
    out = np.asarray(scale_to_bounds(u, [-200, -100], [200, 100]))
    assert np.allclose(out, [[-200, 0], [200, -50]])


def test_gp_callable_model_interface():
    x = latin_hypercube(jax.random.key(0), 64, 2)
    gp = fit_gp(x, _func(x), steps=80)
    out = gp(jnp.array([0.3, 0.4]))  # UM-Bridge style single-point call
    assert out.shape == (1,)


def test_matern_kernel_psd():
    key = jax.random.key(3)
    x = jax.random.normal(key, (40, 3))
    p = GPParams(jnp.zeros(3), jnp.zeros(()), jnp.zeros(()))
    k = np.asarray(matern52(x, x, p))
    eig = np.linalg.eigvalsh(k)
    assert eig.min() > -1e-4


def _fit_small(seed):
    x = latin_hypercube(jax.random.key(seed), 32, 2)
    return fit_gp(x, _func(x), steps=10)


def test_posterior_mean_compiles_once_per_batch_size():
    gp = _fit_small(0)
    thetas = latin_hypercube(jax.random.key(5), 4, 2)
    first = gp.batch_call(thetas)
    n = posterior_mean._cache_size()
    again = gp.batch_call(thetas)
    assert posterior_mean._cache_size() == n
    assert np.array_equal(np.asarray(first), np.asarray(again))


def test_refitted_gps_share_one_program_per_batch_size():
    """The trained state is a jit argument, not a constant baked into the
    program: a GP fitted on other data reuses the compiled programs."""
    gp_a, gp_b = _fit_small(0), _fit_small(1)
    assert not np.array_equal(np.asarray(gp_a.alpha), np.asarray(gp_b.alpha))
    thetas = latin_hypercube(jax.random.key(6), 3, 2)  # (3, 2) x 32 points: new here
    n = posterior_mean._cache_size()
    out_a = gp_a.batch_call(thetas)
    assert posterior_mean._cache_size() == n + 1
    out_b = gp_b.batch_call(thetas)
    assert posterior_mean._cache_size() == n + 1
    assert not np.array_equal(np.asarray(out_a), np.asarray(out_b))
    gp_b(thetas[0])
    m = posterior_mean._cache_size()
    gp_a(thetas[1])
    assert posterior_mean._cache_size() == m
