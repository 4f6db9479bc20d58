"""The plain references agree with the program's own forwards at small
sizes on the CPU."""
import numpy as np
import pytest

from bench.reference import qwen2, tohoku
from bench.tests import small

THETAS = np.array([[0.0, 0.0], [120.0, -80.0], [-150.0, 60.0]], np.float32)


@pytest.fixture(scope="module")
def cfg():
    return small.mlda_config()


def program_scenario(cfg, grid):
    from repro.swe import TohokuScenario

    return TohokuScenario(nx=grid[0], ny=grid[1], t_end=cfg["scenario"]["t_end_s"])


@pytest.mark.parametrize("level", ["coarse_grid", "fine_grid"])
def test_swe_reference_matches_the_program(cfg, level):
    import jax
    import jax.numpy as jnp

    ref = tohoku.Forward(cfg["scenario"], cfg[level], block=4)
    forward = program_scenario(cfg, cfg[level]).build_forward()
    assert (ref.n_steps, ref.dt) == (forward.n_steps, pytest.approx(forward.dt))
    prog = jax.jit(forward)
    got = np.stack([np.asarray(prog(jnp.asarray(t))) for t in THETAS])
    sigma = tohoku.noise_sigma(cfg["scenario"])
    assert np.max(np.abs(ref(THETAS) - got) / sigma) < 1e-3


def test_gp_reference_matches_the_program(cfg):
    import jax
    import jax.numpy as jnp

    from repro.swe.scenario import TohokuInverseProblem, train_level0_gp

    scen = program_scenario(cfg, cfg["coarse_grid"])
    coarse = jax.jit(scen.build_forward())
    prob = TohokuInverseProblem(scenario_fine=scen)
    gp = train_level0_gp(coarse, prob, n_train=cfg["gp"]["train_points"], steps=cfg["gp"]["adam_steps"])
    ref = tohoku.GaussianProcess(cfg["scenario"], cfg["gp"], tohoku.Forward(cfg["scenario"], cfg["coarse_grid"]))
    sigma = tohoku.noise_sigma(cfg["scenario"])
    got = np.asarray(gp.batch_call(jnp.asarray(THETAS)))
    assert np.max(np.abs(ref(THETAS) - got) / sigma) < 0.05


def test_observations_match_the_program(cfg):
    import jax

    from repro.swe.scenario import TohokuInverseProblem

    scen = program_scenario(cfg, cfg["fine_grid"])
    prob = TohokuInverseProblem(scenario_fine=scen)
    y = prob.generate_observations(jax.jit(scen.build_forward()))
    ref = tohoku.observations(cfg["scenario"], tohoku.Forward(cfg["scenario"], cfg["fine_grid"]))
    assert np.max(np.abs(ref - y) / tohoku.noise_sigma(cfg["scenario"])) < 1e-3


def test_qwen2_reference_matches_the_program_forward():
    import jax
    import jax.numpy as jnp

    from bench.drivers import lm as lm_driver
    from repro.models import lm

    cfg = small.lm_config()
    params = lm_driver.make_weights(cfg, 5)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], size=20).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(lm.forward(params, lm_driver.arch_config(cfg), {"tokens": jnp.asarray(tokens)[None]}))[0]
    got = np.asarray(qwen2.logits(params, jnp.asarray(tokens), cfg=tuple(sorted(lm_driver.model_sizes(cfg).items()))))
    assert np.max(np.abs(got - want)) < 1e-4 * max(1.0, np.max(np.abs(want)))


def test_qwen2_padding_hides_nothing():
    import jax.numpy as jnp

    from bench.drivers import lm as lm_driver

    cfg = small.lm_config()
    params = lm_driver.make_weights(cfg, 5)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg["vocab_size"], size=9).astype(np.int32)
    key = tuple(sorted(lm_driver.model_sizes(cfg).items()))
    full = np.asarray(qwen2.logits(params, jnp.asarray(prompt), cfg=key))
    served = full[-1:].argmax(-1)  # one greedy token: gap 0 at 9 and at 30 positions
    for pad in (10, 30):
        gaps = qwen2.served_gaps(params, prompt, np.concatenate([served, [0]])[:1], lm_driver.model_sizes(cfg), pad)
        assert gaps.shape == (1,) and gaps[0] == pytest.approx(0.0, abs=1e-5)
