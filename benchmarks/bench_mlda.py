"""Paper Table 1 reproduction + ensemble pool-utilization benchmark.

Part A (Table 1): runs the CPU-scaled Tōhoku inversion (GP / coarse SWE /
fine SWE) single-chain and reports per-level eval counts, mean eval
seconds, acceptance rates, E[phi] and V[phi] per coordinate — the exact
columns of the paper's Table 1 — plus the variance-reduction check.

Part B (utilization): the same hierarchy behind a load balancer, driven by
the ensemble runner with 1 chain and then ``n_chains >= 4``.  A single
blocking chain can keep at most one of the pool's servers busy at a time;
multiplexed chains overlap one chain's coarse subchains with another's
fine solves, so pool utilization (busy-seconds / (wall x n_servers)) must
rise with chain count — the scheduling win of Seelinger et al.
(arXiv:2107.14552) that motivates the async pipeline.  The section also
reports the *device-resident* mode (DESIGN.md §9): coarse subchains fused
on device, only fine solves through the balancer's pool.

Part C (chain scaling): surrogate-level chain-steps/s at C = 1/4/16/64 —
the fused ``(C,)``-vmapped device kernel vs C independent Python step
machines.  The device curve should be near-flat in C (one executable
advances all chains); the step machine is host-bound and scales linearly
in cost.  ``--smoke --min-chain-speedup`` gates the C=16 speedup in CI.

Writes ``benchmarks/BENCH_mlda.json`` so the perf trajectory is tracked;
``--smoke`` runs a scaled-down workload (CI) and exits non-zero if the
ensemble does not reach 2x the single-chain utilization or the device
kernel misses the chain-scaling gate.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import List

import jax.numpy as jnp
import numpy as np

from repro.configs.tohoku_mlda import CPU, MLDAWorkloadConfig
from repro.core import GaussianRandomWalk, MLDASampler, balanced_mlda
from repro.swe import make_level_servers
from repro.swe.inversion import build_inversion, level_densities

# The CPU workload's grids (so the forward-solve cost spread is the real
# one: fine ~70 ms >> coarse ~10 ms >> GP ~1 ms) with the GP training and
# sample budgets shrunk to CI-sized wall time.
SMOKE = MLDAWorkloadConfig(
    name="smoke",
    coarse_grid=CPU.coarse_grid,
    fine_grid=CPU.fine_grid,
    t_end_s=CPU.t_end_s,
    gp_train_points=16,
    gp_opt_steps=20,
    n_chains=6,
    n_fine_samples=8,
    subchain_lengths=(3, 2),
    rw_step_km=6.0,  # higher acceptance -> subchains move -> fine solves flow
    speculative_prefetch=True,
)


def build(w: MLDAWorkloadConfig):
    inv = build_inversion(w)
    h = inv.hierarchy
    return inv.problem, inv.gp, h["forward_coarse"], h["forward_fine"]


def run_table1(w: MLDAWorkloadConfig, prob, gp, f_coarse, f_fine, n_fine: int):
    def density(forward):
        def lp(t):
            pr = prob.log_prior(t)
            if not np.isfinite(pr):
                return float("-inf")
            return pr + prob.log_likelihood(np.asarray(forward(jnp.asarray(t))))

        return lp

    sampler = MLDASampler(
        [density(gp), density(f_coarse), density(f_fine)],
        GaussianRandomWalk(w.rw_step_km),
        list(w.subchain_lengths),
    )
    chain = sampler.sample(np.array([60.0, 60.0]), n_fine, np.random.default_rng(0))
    return sampler, chain


def run_utilization(
    w: MLDAWorkloadConfig, prob, gp, f_coarse, f_fine, n_chains: int, n_fine: int
):
    """Pool utilization of an n-chain ensemble on a fresh balancer.

    The 1-chain run keeps speculation off — it is the paper-faithful
    blocking client this PR's async pipeline is measured against; the
    multi-chain run uses the full pipeline (ensemble multiplexing +
    configured speculative prefetch).  Batched coalescing stays OFF here
    on purpose: this benchmark isolates the scheduling-overlap win, and a
    coalesced batch books one busy interval for B solves, which would mix
    the two effects — ``bench_batch.py`` measures the batching win.
    """
    import dataclasses

    w = dataclasses.replace(w, batch_solves=False)
    servers = make_level_servers(w, gp, f_coarse, f_fine)
    runner, lb = balanced_mlda(
        servers,
        prob.log_likelihood,
        prob.log_prior,
        GaussianRandomWalk(w.rw_step_km),
        list(w.subchain_lengths),
        policy=w.balancer_policy,
        n_chains=n_chains,
        ensemble_seed=w.ensemble_seed,
        speculative=w.speculative_prefetch and n_chains > 1,
        as_runner=True,
    )
    t0 = time.monotonic()
    result = runner.run(
        lambda c, rng: prob.sample_prior(rng)[0] * 0.5, n_fine
    )
    wall = time.monotonic() - t0
    summary = lb.summary()
    busy = sum(summary["per_server_uptime"].values())
    lb.shutdown()
    util = busy / (wall * len(servers)) if wall > 0 else 0.0
    spec = result.summary()
    return {
        "n_chains": n_chains,
        "n_servers": len(servers),
        "wall_s": wall,
        "busy_s": busy,
        "utilization": util,
        "n_requests": summary["n_requests"],
        "mean_idle_s": summary["mean_idle_s"],
        "gelman_rubin": spec["gelman_rubin"],
        "n_speculated": spec["n_speculated"],
        "n_spec_hits": spec["n_spec_hits"],
        "spec_discarded": [lvl["n_spec_discarded"] for lvl in spec["levels"]],
    }


def _jax_densities(prob, gp, f_coarse):
    """Traceable per-level log densities for the device kernel, plus a
    float-valued host twin of the surrogate density for the step-machine
    baseline — same math, per-step Python dispatch."""
    lp_gp, lp_coarse = level_densities(prob, gp, f_coarse)

    def lp_gp_host(t):
        return float(lp_gp(jnp.asarray(np.asarray(t, np.float32))))

    return lp_gp, lp_coarse, lp_gp_host


def run_utilization_device(
    w: MLDAWorkloadConfig, prob, gp, f_coarse, f_fine, n_chains: int, n_fine: int
):
    """Device-resident counterpart of :func:`run_utilization`.

    GP and coarse subchains run as one fused device kernel, so only fine
    (level-2) solves reach the balancer — the pool is just the fine
    servers, and utilization is measured against that pool.  Reported
    alongside the step-machine figures so the artifact shows both modes.
    """
    import dataclasses

    w = dataclasses.replace(w, batch_solves=False)
    servers = [
        s
        for s in make_level_servers(w, gp, f_coarse, f_fine)
        if "level2" in s.capacity_tags
    ]
    lp_gp, lp_coarse, _ = _jax_densities(prob, gp, f_coarse)
    runner, lb = balanced_mlda(
        servers,
        prob.log_likelihood,
        prob.log_prior,
        GaussianRandomWalk(w.rw_step_km),
        list(w.subchain_lengths),
        policy=w.balancer_policy,
        ensemble_seed=w.ensemble_seed,
        device_resident=True,
        device_densities=[lp_gp, lp_coarse],
        device_chunk=w.device_chunk,
    )
    rng = np.random.default_rng(w.ensemble_seed)
    theta0 = (prob.sample_prior(rng, n_chains) * 0.5).astype(np.float32)
    t0 = time.monotonic()
    result = runner.run(theta0, n_fine)
    wall = time.monotonic() - t0
    summary = lb.summary()
    busy = sum(summary["per_server_uptime"].values())
    lb.shutdown()
    util = busy / (wall * len(servers)) if wall > 0 else 0.0
    totals = result.level_totals()
    return {
        "n_chains": n_chains,
        "n_servers": len(servers),
        "wall_s": wall,
        "busy_s": busy,
        "utilization": util,
        "n_requests": summary["n_requests"],
        "fine_evals": totals[-1]["n_evals"],
    }


def run_chain_scaling(
    w: MLDAWorkloadConfig,
    prob,
    gp,
    f_coarse,
    smoke: bool,
    chain_counts=(1, 4, 16, 64),
):
    """Surrogate-level chain-steps/s: fused device kernel vs step machines.

    Both sides run plain Metropolis on the GP surrogate density.  The
    device side advances all C chains in one vmapped executable (timed
    post-compile over a second ``advance`` launch); the baseline drives C
    independent :class:`MLDASampler` machines from Python.  Per-C step
    budgets differ (the step machine is orders of magnitude slower) —
    rates, not walls, are compared.
    """
    from repro.core.mlda_jax import make_device_ensemble

    lp_gp, _, lp_host = _jax_densities(prob, gp, f_coarse)
    dev_steps = 64 if smoke else 512
    mach_steps = 8 if smoke else 64
    rng = np.random.default_rng(w.ensemble_seed)
    sweep = []
    for n_chains in chain_counts:
        theta0 = (prob.sample_prior(rng, n_chains) * 0.5).astype(np.float32)
        ens = make_device_ensemble(
            [lp_gp], [], w.rw_step_km, cache_key=("bench_chain_scaling",)
        )
        state = ens.init(theta0, seed=w.ensemble_seed)
        state, thetas, _ = ens.advance(state, dev_steps)  # compile + warm
        np.asarray(thetas)
        t0 = time.monotonic()
        state, thetas, _ = ens.advance(state, dev_steps)
        np.asarray(thetas)  # host sync: launch really finished
        dev_s = time.monotonic() - t0
        t0 = time.monotonic()
        for c in range(n_chains):
            samp = MLDASampler([lp_host], GaussianRandomWalk(w.rw_step_km), [])
            samp.sample(theta0[c], mach_steps, np.random.default_rng(c))
        mach_s = time.monotonic() - t0
        dev_rate = n_chains * dev_steps / max(dev_s, 1e-9)
        mach_rate = n_chains * mach_steps / max(mach_s, 1e-9)
        sweep.append(
            {
                "n_chains": n_chains,
                "device_steps": dev_steps,
                "machine_steps": mach_steps,
                "device_s": dev_s,
                "machine_s": mach_s,
                "device_steps_per_s": dev_rate,
                "machine_steps_per_s": mach_rate,
                "speedup": dev_rate / max(mach_rate, 1e-9),
            }
        )
    return sweep


def main(smoke: bool = False, n_fine: int = 0, ensemble_chains: int = 0):
    w = SMOKE if smoke else CPU
    n_fine = n_fine or w.n_fine_samples
    ensemble_chains = ensemble_chains or max(4, w.n_chains)

    prob, gp, f_coarse, f_fine = build(w)
    # Warm the jit caches so compile time doesn't pollute utilization.
    _ = np.asarray(f_fine(jnp.asarray([60.0, 60.0])))
    _ = np.asarray(f_coarse(jnp.asarray([60.0, 60.0])))
    _ = np.asarray(gp(jnp.asarray([60.0, 60.0])))

    sampler, chain = run_table1(w, prob, gp, f_coarse, f_fine, n_fine)
    rows = []
    table1 = []
    for r in sampler.stats_table():
        e = r["E_phi"] or [float("nan")] * 2
        v = r["V_phi"] or [float("nan")] * 2
        table1.append(r)
        rows.append(f"mlda_level{r['level']}_evals,{r['n_evals']},count")
        rows.append(
            f"mlda_level{r['level']}_mean_eval,{r['mean_eval_s'] * 1e6:.0f},us"
        )
        rows.append(
            f"mlda_level{r['level']}_acceptance,{r['acceptance_rate']:.3f},rate"
        )
        rows.append(f"mlda_level{r['level']}_E,({e[0]:.1f};{e[1]:.1f}),km")
        rows.append(f"mlda_level{r['level']}_V,({v[0]:.0f};{v[1]:.0f}),km2")
    # variance reduction across levels (paper §6.1)
    from repro.core.diagnostics import variance_reduction_check

    samples = [np.asarray(r.samples) for r in sampler.levels if r.samples]
    vr = variance_reduction_check(samples)
    rows.append(f"mlda_variance_reduction,{all(vr)},bool")
    rows.append(
        f"mlda_fine_posterior_mean,({chain.mean(0)[0]:.1f};{chain.mean(0)[1]:.1f}),km"
    )

    single = run_utilization(w, prob, gp, f_coarse, f_fine, 1, n_fine)
    multi = run_utilization(
        w, prob, gp, f_coarse, f_fine, ensemble_chains, n_fine
    )
    device = run_utilization_device(
        w, prob, gp, f_coarse, f_fine, ensemble_chains, n_fine
    )
    ratio = multi["utilization"] / max(single["utilization"], 1e-12)
    rows.append(f"mlda_pool_util_1chain,{single['utilization']:.3f},frac")
    rows.append(
        f"mlda_pool_util_{ensemble_chains}chain,{multi['utilization']:.3f},frac"
    )
    rows.append(f"mlda_pool_util_ratio,{ratio:.2f},x")
    rows.append(
        f"mlda_pool_util_device,{device['utilization']:.3f},frac"
    )
    rows.append(f"mlda_spec_hits,{multi['n_spec_hits']},count")
    rows.append(f"mlda_spec_attempts,{multi['n_speculated']},count")

    scaling = run_chain_scaling(w, prob, gp, f_coarse, smoke)
    speedup16 = 0.0
    for entry in scaling:
        rows.append(
            f"mlda_chain_dev_rate_{entry['n_chains']},"
            f"{entry['device_steps_per_s']:.0f},steps/s"
        )
        rows.append(
            f"mlda_chain_speedup_{entry['n_chains']},{entry['speedup']:.1f},x"
        )
        if entry["n_chains"] == 16:
            speedup16 = entry["speedup"]

    payload = {
        "workload": w.name,
        "n_fine_samples": n_fine,
        "table1": table1,
        "utilization": {
            "single_chain": single,
            "ensemble": multi,
            "device_resident": device,
            "ratio": ratio,
        },
        "chain_scaling": {
            "sweep": scaling,
            "speedup_at_16": speedup16,
        },
    }
    out_path = os.path.join(os.path.dirname(__file__), "BENCH_mlda.json")
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, default=float)
    rows.append(f"mlda_bench_json,{out_path},path")
    return rows


def _row_value(rows: List[str], name: str) -> float:
    for row in rows:
        if row.startswith(name + ","):
            return float(row.split(",")[1])
    return 0.0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="scaled-down CI workload; fails if ensemble "
                         "utilization ratio < --min-ratio or the C=16 "
                         "chain-scaling speedup < --min-chain-speedup")
    ap.add_argument("--min-ratio", type=float, default=2.0,
                    help="utilization-ratio gate for --smoke (2.0 on idle "
                         "hardware; CI uses a lower bar since contended "
                         "shared runners compress solve overlap)")
    ap.add_argument("--min-chain-speedup", type=float, default=4.0,
                    help="--smoke gate: fused device kernel must reach this "
                         "multiple of the step-machine surrogate-level "
                         "throughput at C=16")
    ap.add_argument("--n-fine", type=int, default=0)
    ap.add_argument("--chains", type=int, default=0)
    args = ap.parse_args()
    out_rows = main(
        smoke=args.smoke, n_fine=args.n_fine, ensemble_chains=args.chains
    )
    for row in out_rows:
        print(row)
    util_ratio = _row_value(out_rows, "mlda_pool_util_ratio")
    if args.smoke and util_ratio < args.min_ratio:
        raise SystemExit(
            f"ensemble pool utilization only {util_ratio:.2f}x the "
            f"single-chain figure (expected >= {args.min_ratio}x)"
        )
    chain_speedup = _row_value(out_rows, "mlda_chain_speedup_16")
    if args.smoke and chain_speedup < args.min_chain_speedup:
        raise SystemExit(
            f"device-resident chain stepping only {chain_speedup:.1f}x the "
            f"step machine at C=16 (expected >= {args.min_chain_speedup}x)"
        )
