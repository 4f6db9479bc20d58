"""The paper's Tōhoku MLDA inversion (§6) as two calls: build, then sample.

:func:`build_inversion` assembles the three-level hierarchy (GP surrogate /
coarse PDE / fine PDE) for an :class:`~repro.configs.tohoku_mlda.
MLDAWorkloadConfig`; :func:`sample_inversion` runs the config's chains
through the load balancer and returns the result with the balancer's
summary.  ``examples/tsunami_inversion.py``, ``repro.launch.export`` and
``chip_smoke.py`` all drive the workload through these two calls.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from .scenario import TohokuInverseProblem, TohokuScenario, make_hierarchy, train_level0_gp
from .servers import make_level_servers, make_remote_level_servers


@dataclass
class Inversion:
    """A built hierarchy: scenarios, forwards, inverse problem, surrogate."""

    workload: Any  # MLDAWorkloadConfig
    fine: TohokuScenario
    coarse: TohokuScenario
    hierarchy: Dict[str, Any]
    gp: Optional[Callable]  # None when the level pools are remote
    gp_seconds: float = 0.0

    @property
    def problem(self) -> TohokuInverseProblem:
        return self.hierarchy["problem"]

    def level_servers(self) -> List:
        """Fresh in-process level pools (one balancer's worth)."""
        h, w = self.hierarchy, self.workload
        return make_level_servers(
            w, self.gp, h["forward_coarse"], h["forward_fine"],
            batch_forwards=(
                None, h["forward_coarse_batch"], h["forward_fine_batch"]
            ) if w.batch_solves else None,
        )

    def device_densities(self) -> List[Callable]:
        """Traceable log-posteriors of levels 0 and 1 for the fused device
        kernel of the device-resident mode (DESIGN.md §9)."""
        return level_densities(self.problem, self.gp, self.hierarchy["forward_coarse"])


def level_densities(
    prob: TohokuInverseProblem, gp: Callable, f_coarse: Callable
) -> List[Callable]:
    """``[log pi_0, log pi_1]``: GP-surrogate and coarse-PDE log-posteriors,
    both traceable, so they compose into one fused vmapped chain step."""

    def lp_gp(t):
        return prob.log_prior_jax(t) + prob.log_likelihood_jax(gp(t))

    def lp_coarse(t):
        return prob.log_prior_jax(t) + prob.log_likelihood_jax(f_coarse(t))

    return [lp_gp, lp_coarse]


def build_inversion(w) -> Inversion:
    """Scenarios + hierarchy (fine solve for ``y_obs``) + level-0 GP.

    With ``w.remote_servers`` the exporting processes own the level pools,
    GP included, so no surrogate is trained here.
    """
    fine = TohokuScenario(nx=w.fine_grid[0], ny=w.fine_grid[1], t_end=w.t_end_s)
    coarse = TohokuScenario(nx=w.coarse_grid[0], ny=w.coarse_grid[1], t_end=w.t_end_s)
    h = make_hierarchy(fine=fine, coarse=coarse)
    gp, gp_s = None, 0.0
    if not w.remote_servers:
        t0 = time.monotonic()
        gp = train_level0_gp(
            h["forward_coarse"], h["problem"],
            n_train=w.gp_train_points, steps=w.gp_opt_steps,
        )
        jax.block_until_ready(gp.alpha)
        gp_s = time.monotonic() - t0
    return Inversion(w, fine, coarse, h, gp, gp_s)


@dataclass
class InversionRun:
    """One sampling run: chains, the balancer's summary, wall time."""

    result: Any  # repro.ensemble.EnsembleResult
    summary: Dict[str, Any]
    wall_s: float
    leaked_threads: int  # threads still alive after the balancer shut down


def sample_inversion(
    inv: Inversion,
    *,
    n_chains: int,
    policy: str,
    n_fine_samples: Optional[int] = None,
) -> InversionRun:
    """Run ``n_chains`` MLDA chains through one balancer, then shut it down.

    Step-machine chains (the default) dispatch every level's solves through
    the balancer; with ``workload.device_resident`` the coarse subchains of
    all chains run as one fused device kernel and only fine solves reach the
    balancer.  Remote transports are closed before returning.
    """
    from repro.core import GaussianRandomWalk, balanced_mlda

    w, prob = inv.workload, inv.problem
    if w.device_resident and w.remote_servers:
        raise ValueError(
            "device-resident chains evaluate the GP and coarse levels on this "
            "process's device; they cannot run against remote level pools"
        )
    n = n_fine_samples or w.n_fine_samples
    threads_before = threading.active_count()
    if w.remote_servers:
        servers = make_remote_level_servers(w, w.remote_servers)
    else:
        servers = inv.level_servers()
    common = dict(
        policy=policy,
        batchable_levels=w.batchable_levels,
        ensemble_seed=w.ensemble_seed,
        **w.balancer_kwargs(),
    )
    if w.device_resident:
        fine_servers = [s for s in servers if "level2" in s.capacity_tags]
        runner, lb = balanced_mlda(
            fine_servers, prob.log_likelihood, prob.log_prior,
            GaussianRandomWalk(w.rw_step_km), list(w.subchain_lengths),
            device_resident=True,
            device_densities=inv.device_densities(),
            device_chunk=w.device_chunk,
            **common,
        )
        rng = np.random.default_rng(w.ensemble_seed)
        theta0 = (prob.sample_prior(rng, n_chains) * 0.5).astype(np.float32)
    else:
        runner, lb = balanced_mlda(
            servers, prob.log_likelihood, prob.log_prior,
            GaussianRandomWalk(w.rw_step_km), list(w.subchain_lengths),
            n_chains=n_chains,
            speculative=w.speculative_prefetch,
            as_runner=True,
            **common,
        )
        theta0 = lambda c, rng: prob.sample_prior(rng)[0] * 0.5
    try:
        t0 = time.monotonic()
        result = runner.run(theta0, n)
        wall = time.monotonic() - t0
        summary = lb.summary()
    finally:
        lb.shutdown()  # joins the dispatcher + worker pool
        if w.remote_servers:  # one shared transport per endpoint: close each once
            for tr in {id(s.transport): s.transport for s in servers}.values():
                tr.close()
    return InversionRun(
        result=result,
        summary=summary,
        wall_s=wall,
        leaked_threads=threading.active_count() - threads_before,
    )
