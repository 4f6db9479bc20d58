"""End-to-end behaviour tests for the paper's system (deliverable (c)).

A compressed version of examples/tsunami_inversion.py with assertions on
the paper's §6 claims: surrogate fidelity, posterior location, variance
reduction, balancer idle times under the MLDA dependency structure.
"""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GaussianRandomWalk, LoadBalancer, MLDASampler, Server
from repro.core.diagnostics import variance_reduction_check
from repro.core.mlda import BalancedDensity
from repro.swe import TohokuScenario, make_hierarchy, train_level0_gp


@pytest.fixture(scope="module")
def hierarchy():
    fine = TohokuScenario(nx=48, ny=48, t_end=2 * 3600.0)
    coarse = TohokuScenario(nx=24, ny=24, t_end=2 * 3600.0)
    h = make_hierarchy(fine=fine, coarse=coarse)
    h["gp"] = train_level0_gp(h["forward_coarse"], h["problem"], n_train=96, steps=120)
    return h


def test_gp_surrogate_tracks_coarse_model(hierarchy):
    gp, f_coarse = hierarchy["gp"], hierarchy["forward_coarse"]
    prob = hierarchy["problem"]
    rng = np.random.default_rng(0)
    errs = []
    for p in prob.sample_prior(rng, 6):
        g = np.asarray(gp(jnp.asarray(p)))
        c = np.asarray(f_coarse(jnp.asarray(p)))
        errs.append(np.abs(g - c).max())
    assert max(errs) < 0.05, f"GP surrogate inaccurate: {errs}"


def test_mlda_posterior_recovers_source(hierarchy):
    """Paper Fig. 7: posterior concentrates near the (0,0) reference."""
    prob = hierarchy["problem"]
    gp, f_coarse, f_fine = (
        hierarchy["gp"], hierarchy["forward_coarse"], hierarchy["forward_fine"],
    )

    def density(forward):
        def lp(t):
            pr = prob.log_prior(t)
            if not np.isfinite(pr):
                return float("-inf")
            return pr + prob.log_likelihood(np.asarray(forward(jnp.asarray(t))))

        return lp

    s = MLDASampler(
        [density(gp), density(f_coarse), density(f_fine)],
        GaussianRandomWalk(15.0),
        [5, 3],
    )
    chain = s.sample(np.array([60.0, 60.0]), 40, np.random.default_rng(1))
    post = chain[8:]
    dist = np.linalg.norm(post.mean(0) - np.asarray(prob.theta_true))
    assert dist < 80.0, f"posterior mean {post.mean(0)} too far from truth"
    # the bulk of evaluations happened at the cheap levels (Table 1)
    t = s.stats_table()
    assert t[0]["n_evals"] > t[2]["n_evals"]


def test_variance_reduction_and_balancer_idle(hierarchy):
    """Paper §6: variance reduction across levels + ~ms idle times."""
    prob = hierarchy["problem"]
    gp, f_coarse, f_fine = (
        hierarchy["gp"], hierarchy["forward_coarse"], hierarchy["forward_fine"],
    )
    lb = LoadBalancer(
        [
            Server(lambda t: gp(jnp.asarray(t)), name="gp", capacity_tags=("level0",)),
            Server(lambda t: f_coarse(jnp.asarray(t)), name="coarse",
                   capacity_tags=("level1",)),
            Server(lambda t: f_fine(jnp.asarray(t)), name="fine",
                   capacity_tags=("level2",)),
        ]
    )

    def make_sampler():
        dens = [
            BalancedDensity(lb, f"level{l}", prob.log_likelihood, prob.log_prior)
            for l in range(3)
        ]
        return MLDASampler(dens, GaussianRandomWalk(15.0), [4, 2])

    samplers = [make_sampler() for _ in range(2)]
    threads = [
        threading.Thread(
            target=lambda s=s, c=c: s.sample(
                np.array([40.0, -40.0]), 10, np.random.default_rng(c)
            )
        )
        for c, s in enumerate(samplers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    sets = [
        np.concatenate([np.asarray(s.levels[l].samples) for s in samplers])
        for l in range(3)
    ]
    vr = variance_reduction_check(sets)
    assert vr[-1], "no variance reduction at the finest correction"

    s = lb.summary()
    assert s["n_requests"] > 50
    # mean idle time is small relative to a coarse solve (paper Fig. 9)
    assert s["mean_idle_s"] < 0.25


@pytest.mark.parametrize("device_resident", [False, True], ids=["step_machines", "device_resident"])
def test_inversion_build_and_sample(device_resident):
    """The shared build/sample calls the example, export and chip smoke use."""
    import dataclasses

    from repro.configs.tohoku_mlda import CPU
    from repro.swe.inversion import build_inversion, sample_inversion

    w = dataclasses.replace(
        CPU, coarse_grid=(16, 16), fine_grid=(24, 24), t_end_s=1800.0,
        gp_train_points=24, gp_opt_steps=10, device_resident=device_resident,
    )
    inv = build_inversion(w)
    assert inv.gp is not None and inv.gp.x_train.shape[0] == 24
    run = sample_inversion(inv, n_chains=3, policy="fifo", n_fine_samples=4)
    assert run.result.chains.shape == (3, 4, 2)
    assert not run.result.failures and run.summary["failures"] == 0
    assert np.all(np.isfinite(run.result.chains))
    assert run.leaked_threads <= 0
    assert run.result.level_totals()[-1]["n_evals"] > 0
    # Device-resident chains send only fine solves through the balancer.
    assert (run.summary["batch_histogram"].keys() <= {"level2"}) == device_resident


def test_device_resident_rejects_remote_pools():
    import dataclasses

    from repro.configs.tohoku_mlda import CPU
    from repro.swe.inversion import Inversion, sample_inversion

    w = dataclasses.replace(CPU, device_resident=True, remote_servers=("127.0.0.1:1",))
    inv = Inversion(w, None, None, {"problem": None}, None)
    with pytest.raises(ValueError, match="remote"):
        sample_inversion(inv, n_chains=2, policy="fifo")
