"""End-to-end reproduction of the paper's experiment (§6, CPU-scaled).

Pipeline: synthetic Tōhoku scenario -> observations from the fine model at
(0, 0) -> GP surrogate trained on LHS draws of the coarse model (level 0)
-> 3-level MLDA through the load balancer, multiple chains multiplexed by
the ensemble driver (``repro.ensemble.EnsembleRunner``: one thread keeps
every chain's step machine fed, so coarse subchains of one chain overlap
the fine solves of another on the shared server pool) -> posterior vs the
known source + per-level Table-1 stats + split-R-hat/ESS cross-chain
diagnostics + Fig. 9 idle times + the Fig. 6 time-series GP.

Batched solves (``MLDAWorkloadConfig.batch_solves``, default on): every
level's servers are ``BatchServer``s, so same-level solves pending from
different chains coalesce into ONE stacked evaluation — a single vmapped
AOT executable launch for the whole batch (GP: one kernel assembly; SWE:
one fused batched time loop, cached per power-of-two batch size up to
``max_batch``).  The dispatcher sizes its coalescing window adaptively
from the level's EWMA service time, capped at ``batch_window_s``; chains
are bit-identical (fp32) to per-request dispatch either way, and the
realised batch sizes print at the end (``batch_histogram``).  Disable
with ``batch_solves=False`` to compare; ``benchmarks/bench_batch.py``
measures the throughput win.

Remote serving (``--remote host:port[,host:port]``, DESIGN.md §11): the
level pools live in *other processes* — each endpoint runs
``python -m repro.launch.export`` — and this process builds
``RemoteBatchServer`` replicas over the pipelined binary transport
instead of in-process servers.  Coalesced batches cross the wire as one
framed call; telemetry splits wire time from remote service time
(``wire_split`` prints at the end).  ``--remote-json`` switches to the
UM-Bridge HTTP/JSON interop mode for comparison.

Run:  PYTHONPATH=src python examples/tsunami_inversion.py  (~5-10 min on a CPU)
"""
import argparse
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.tohoku_mlda import CONFIGS
from repro.core import available_policies
from repro.core.diagnostics import telescoping_estimate, variance_reduction_check
from repro.launch.compile_cache import enable_compile_cache
from repro.swe.inversion import build_inversion, sample_inversion


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="cpu", choices=list(CONFIGS))
    ap.add_argument("--chains", type=int, default=0, help="override chain count")
    ap.add_argument(
        "--policy",
        default="",
        choices=[""] + available_policies(),
        help="scheduling policy (default: the workload's balancer_policy)",
    )
    ap.add_argument(
        "--remote",
        default="",
        help="comma-separated host:port endpoints (repro.launch.export "
        "processes) to evaluate on instead of in-process pools",
    )
    ap.add_argument(
        "--remote-json",
        action="store_true",
        help="use the UM-Bridge HTTP/JSON interop mode instead of binary framing",
    )
    args = ap.parse_args()
    enable_compile_cache()
    w = CONFIGS[args.workload]
    if args.remote:
        endpoints = tuple(a.strip() for a in args.remote.split(",") if a.strip())
        w = replace(w, remote_servers=endpoints, remote_binary=not args.remote_json)
    n_chains = args.chains or w.n_chains
    policy = args.policy or w.balancer_policy

    print(f"[1/4] building {w.name} hierarchy "
          f"(coarse {w.coarse_grid}, fine {w.fine_grid})"
          + ("" if w.remote_servers else
             f" + level-0 GP on {w.gp_train_points} LHS coarse solves"))
    inv = build_inversion(w)
    prob, coarse = inv.problem, inv.coarse
    print(f"      y_obs = {np.round(prob.y_obs, 4)} (truth at {prob.theta_true})")
    if w.remote_servers:
        # The exporting processes own the level pools (GP included).
        print(f"[2/4] remote serving: dialing {list(w.remote_servers)} "
              f"({'binary' if w.remote_binary else 'UM-Bridge JSON'} mode)")
    else:
        print(f"[2/4] GP trained in {inv.gp_seconds:.1f}s")

    print(f"[3/4] MLDA x {n_chains} chains via the ensemble driver "
          f"(policy={policy}, speculative={w.speculative_prefetch}, "
          f"batch_solves={w.batch_solves})")
    run = sample_inversion(inv, n_chains=n_chains, policy=policy)
    result, s = run.result, run.summary
    samplers = result.samplers

    print(f"[4/4] results ({run.wall_s:.0f}s sampling wall time)")
    burn = max(2, w.n_fine_samples // 5)
    allc = result.pooled(burn)
    print(f"      fine posterior mean = {allc.mean(0).round(1)} km "
          f"(reference (0, 0); paper Fig. 7)")
    print(f"      fine posterior std  = {allc.std(0).round(1)} km")
    print(f"      split-R-hat = {result.gelman_rubin().round(3)}  "
          f"ESS(total) = {np.round(result.ess().sum(0), 1)}")

    # Table 1 analogue (+ speculation telemetry)
    print("      level | evals | acc   | mean eval | spec-discard")
    for row in result.level_totals():
        print(f"        {row['level']}   | {row['n_evals']:5d} "
              f"| {row['acceptance_rate']:.3f} "
              f"| {row['mean_eval_s'] * 1e3:8.1f} ms "
              f"| {row['n_spec_discarded']:5d}")
    spec_total = result.summary()
    print(f"      speculative prefetch: {spec_total['n_spec_hits']}"
          f"/{spec_total['n_speculated']} guesses held")

    sample_sets = [
        np.concatenate([np.asarray(s.levels[lvl].samples) for s in samplers])
        for lvl in range(3)
    ]
    tele = telescoping_estimate(sample_sets)
    print(f"      telescoped mean (Eq. 7) = {tele['telescoped_mean'].round(1)}")
    print(f"      variance reduction up the hierarchy: "
          f"{variance_reduction_check(sample_sets)}")

    print(f"      balancer idle (Fig. 9, policy={policy}): "
          f"mean={s['mean_idle_s'] * 1e3:.2f}ms "
          f"p99={s['p99_idle_s'] * 1e3:.1f}ms max={s['max_idle_s'] * 1e3:.1f}ms")
    if s["batch_histogram"]:
        print(f"      realised batch sizes {{level: {{size: count}}}}: "
              f"{s['batch_histogram']}")
    if s.get("wire_split"):
        print("      wire vs remote service (EWMA ms per call):")
        for key, wsp in sorted(s["wire_split"].items()):
            print(f"        {key}: wire={wsp['wire_ewma_s'] * 1e3:.2f}ms "
                  f"service={wsp['service_ewma_s'] * 1e3:.2f}ms "
                  f"({wsp['calls']} calls)")

    # Fig. 6 analogue: GP over the full probe-0 time series.
    print("      fitting Fig. 6 time-series GP (probe 21418 analogue)")
    series_fwd = jax.jit(coarse.build_series_forward())
    from repro.core.lhs import latin_hypercube, scale_to_bounds
    from repro.core.gp import fit_gp

    lo, hi = prob.prior_bounds()
    xs = scale_to_bounds(latin_hypercube(jax.random.key(7), 32, 2), lo, hi)
    ys = jax.lax.map(series_fwd, xs, batch_size=8)
    ts_gp = fit_gp(xs, ys, steps=60)
    post_series = ts_gp(jnp.asarray(allc.mean(0)))
    print(f"      reconstructed series: len={post_series.shape[0]}, "
          f"max SSHA={float(post_series.max()):.3f} m")


if __name__ == "__main__":
    main()
