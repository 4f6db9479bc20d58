"""Driver of the Tōhoku MLDA cells: chains through ``balanced_mlda``.

Set-up builds the paper's hierarchy and GP (:func:`repro.swe.inversion.
build_inversion`), the level pools (:func:`repro.swe.servers.
make_level_servers`) with every batched forward wrapped in a tap that
records what it served, and the runner.  It warms every padded batch size
each level can see, then runs a short warm-up segment whose pace sets how
many fine samples per chain the window asks for, so that one
``runner.run`` call lasts about ``--seconds``.  The window is that call.

Step-machine chains (``device_resident: false``) send every level's
solves through the balancer.  Device-resident chains run all chains'
coarse subchains as one fused launch per step and send only the moved
chains' fine proposals; their coarse log-posterior of each proposal is
recorded too.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

import numpy as np

from bench import harness
from bench import traffic as gen
from bench import work
from bench.harness import Check, Outcome, now, span
from bench.windowed import hist_delta

LEVELS = (0, 1, 2)


def workload_config(cfg: Dict[str, Any], tr: Dict[str, Any], seed31: int):
    """The program's :class:`MLDAWorkloadConfig` as the configuration file
    states it, with the traffic's chain layout and the run's seed."""
    from repro.configs.tohoku_mlda import MLDAWorkloadConfig

    return MLDAWorkloadConfig(
        name=cfg["name"],
        coarse_grid=tuple(cfg["coarse_grid"]),
        fine_grid=tuple(cfg["fine_grid"]),
        t_end_s=float(cfg["scenario"]["t_end_s"]),
        gp_train_points=int(cfg["gp"]["train_points"]),
        gp_opt_steps=int(cfg["gp"]["adam_steps"]),
        n_chains=int(tr["chains"]),
        subchain_lengths=tuple(int(n) for n in cfg["subchain_lengths"]),
        rw_step_km=float(cfg["rw_step_km"]),
        servers_per_level={int(k): int(v) for k, v in cfg["servers_per_level"].items()},
        balancer_policy=cfg["balancer_policy"],
        max_batch=int(cfg["max_batch"]),
        batch_window_s=float(cfg["batch_window_s"]),
        ensemble_seed=seed31,
        device_resident=bool(tr["device_resident"]),
    )


class Tap:
    """Wraps one level's batched forward: a host span around each call,
    and, while ``on``, a copy of every ``(thetas, outputs)`` it served."""

    def __init__(self, label: str) -> None:
        self.label = label
        self.on = False
        self.calls: List = []
        self._lock = threading.Lock()

    def wrap(self, fn):
        def tapped(thetas):
            with span(f"bench.{self.label}"):
                out = np.asarray(fn(thetas))
            if self.on:
                with self._lock:
                    self.calls.append((np.array(thetas, np.float64), out.astype(np.float64)))
            return out

        return tapped

    def rows(self):
        if not self.calls:
            return np.zeros((0, 2)), np.zeros((0, 4))
        return (
            np.concatenate([t for t, _ in self.calls]),
            np.concatenate([o for _, o in self.calls]),
        )


def pow2_sizes(max_batch: int) -> List[int]:
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    return out + [max_batch]


class MLDACell:
    """One cell's system, built and warmed; :meth:`window` measures it."""

    def __init__(self, ctx: harness.Context, inv=None) -> None:
        """``inv``: an inversion built before for the same configuration
        (it does not depend on the seed); built here when None."""
        import dataclasses

        from repro.swe.inversion import build_inversion
        from repro.swe.servers import make_level_servers

        self.ctx = ctx
        cfg, tr = ctx.cell.config, ctx.cell.traffic
        self.cfg, self.tr = cfg, tr
        self.w = workload_config(cfg, tr, gen.int31(ctx.seed))
        self.resident = bool(tr["device_resident"])
        self.inv = build_inversion(self.w) if inv is None else dataclasses.replace(inv, workload=self.w)
        h = self.inv.hierarchy
        self.raw = [self.inv.gp.batch_call, h["forward_coarse_batch"], h["forward_fine_batch"]]
        self.taps = {lvl: Tap(f"level{lvl}") for lvl in LEVELS}
        forwards = [
            self.taps[lvl].wrap(ctx.tampered(f"level{lvl}", self.raw[lvl])) for lvl in LEVELS
        ]
        self.servers = make_level_servers(
            self.w, self.inv.gp, h["forward_coarse"], h["forward_fine"], batch_forwards=forwards
        )
        self.proposals: List = []  # resident: (psi, logp_psi_low, moved) per step
        self.propose_times: List[float] = []
        self.recording = False
        self._build_runner()
        lo, hi = self.inv.problem.prior_bounds()
        self.starts = gen.chain_starts(tr, lo, hi, ctx.seed)
        self.warm_shapes()

    # -- set-up --------------------------------------------------------------
    def _build_runner(self) -> None:
        from repro.core import GaussianRandomWalk, balanced_mlda

        w, prob = self.w, self.inv.problem
        common = dict(
            policy=w.balancer_policy,
            batchable_levels=w.batchable_levels,
            ensemble_seed=w.ensemble_seed,
            **w.balancer_kwargs(),
        )
        if self.resident:
            fine = [s for s in self.servers if "level2" in s.capacity_tags]
            self.runner, self.lb = balanced_mlda(
                fine, prob.log_likelihood, prob.log_prior,
                GaussianRandomWalk(w.rw_step_km), list(w.subchain_lengths),
                device_resident=True, device_densities=self.inv.device_densities(),
                device_chunk=w.device_chunk, **common,
            )
            self._tap_proposals()
        else:
            self.runner, self.lb = balanced_mlda(
                self.servers, prob.log_likelihood, prob.log_prior,
                GaussianRandomWalk(w.rw_step_km), list(w.subchain_lengths),
                n_chains=w.n_chains, speculative=w.speculative_prefetch,
                as_runner=True, **common,
            )

    def _tap_proposals(self) -> None:
        ens = self.runner.ensemble
        propose = ens.propose

        def tapped(state):
            self.propose_times.append(now())
            with span("bench.propose"):
                state, pending = propose(state)
                moved = np.asarray(pending.moved)
            if self.recording:
                self.proposals.append(
                    (np.asarray(pending.psi, np.float64),
                     np.asarray(pending.logp_psi_low, np.float64), moved)
                )
            return state, pending

        ens.propose = tapped

    def levels_served(self) -> List[int]:
        return [2] if self.resident else list(LEVELS)

    def warm_shapes(self) -> None:
        """Compile, or load from the compile cache, every batch size a
        level's pool can be handed: the GP's posterior mean is one program
        per size 1..max_batch, the PDE levels pad to powers of two.
        Device-resident chains also compile their fused propose / accept
        programs."""
        import jax.numpy as jnp

        mb = self.w.max_batch
        for lvl in self.levels_served():
            sizes = range(1, mb + 1) if lvl == 0 else pow2_sizes(mb)
            for b in sizes:
                thetas = self.starts[np.arange(b) % len(self.starts)]
                np.asarray(self.raw[lvl](jnp.asarray(thetas)))
        if self.resident:
            ens = self.runner.ensemble
            n = len(self.starts)
            state = ens.init(self.starts, seed=self.w.ensemble_seed, logp0=np.zeros(n))
            state, pending = ens.propose(state)
            ens.accept(state, pending, np.zeros(n, np.float32))
            self.propose_times.clear()

    def run_chains(self, starts: np.ndarray, n: int):
        if self.resident:
            return self.runner.run(starts, n)
        return self.runner.run(lambda c, _rng: starts[c], n)

    def calibrate(self) -> int:
        """Run the warm-up segment; return the fine samples per chain that
        make one ``runner.run`` last about ``--seconds``."""
        n_w = int(self.tr["warm_samples"])
        self.propose_times.clear()
        t0 = now()
        res = self.run_chains(self.starts, n_w)
        elapsed = now() - t0
        self.starts = np.asarray(res.chains[:, -1], np.float32)
        seconds = self.ctx.seconds
        if self.resident and len(self.propose_times) >= 2:
            t_first = self.propose_times[0] - t0
            t_step = (self.propose_times[-1] - self.propose_times[0]) / (len(self.propose_times) - 1)
            n = (seconds - t_first) / max(t_step, 1e-6)
        else:
            n = seconds * n_w / max(elapsed, 1e-6)
        return max(1, int(round(n)))

    def probe(self) -> None:
        """Run each compiled fine-solve program once, alone, inside a span
        named for it, so the trace reduction can tell its device events
        from the coarse level's (both programs carry the same name)."""
        import jax.numpy as jnp

        for b in pow2_sizes(self.w.max_batch):
            thetas = self.starts[np.arange(b) % len(self.starts)]
            with span(f"bench.probe.level2.b{b}"):
                np.asarray(self.raw[2](jnp.asarray(thetas)))

    # -- the window ----------------------------------------------------------
    def window(self, n: int) -> Dict[str, Any]:
        ctx = self.ctx
        before = self.lb.summary()
        for t in self.taps.values():
            t.on, t.calls = True, []
        self.proposals.clear()
        self.recording = True
        c0 = ctx.compile_clock.snapshot()
        with ctx.traced():
            if ctx.trace:
                self.probe()
            with span("bench.window"):
                t0 = now()
                result = self.run_chains(self.starts, n)
                t1 = now()
        c1 = ctx.compile_clock.snapshot()
        self.recording = False
        for t in self.taps.values():
            t.on = False
        after = self.lb.summary()
        return dict(t0=t0, t1=t1, result=result, before=before, after=after,
                    compiles=c1[0] - c0[0], compile_s=c1[1] - c0[1], n=n)

    def facts(self, win: Dict[str, Any]) -> Dict[str, Any]:
        """Counts the per-layer readers use: samples, rows per level, and
        the operations the window's evaluations needed."""
        res, before, after = win["result"], win["before"], win["after"]
        samples = int(res.chains.shape[0] * res.chains.shape[1])
        rows = {lvl: hist_delta(before, after, f"level{lvl}") for lvl in LEVELS}
        evals = {lvl: sum(b * c for b, c in rows[lvl].items()) for lvl in LEVELS}
        if self.resident:  # levels 0 and 1 ran inside the fused launches
            for lvl in (0, 1):
                evals[lvl] = int(sum(s.levels[lvl].n_evals for s in res.samplers))
        fine = self.inv.hierarchy["forward_fine_batch"].n_steps
        coarse = self.inv.hierarchy["forward_coarse_batch"].n_steps
        flops = (
            evals[0] * work.gp_predict_flops(self.w.gp_train_points)
            + evals[1] * work.swe_forward_flops(*self.w.coarse_grid, coarse)
            + evals[2] * work.swe_forward_flops(*self.w.fine_grid, fine)
        )
        return {
            "fine_samples": samples,
            "window_s": win["t1"] - win["t0"],
            "batch_sizes": {f"level{lvl}": rows[lvl] for lvl in LEVELS},
            "evals": {f"level{lvl}": evals[lvl] for lvl in LEVELS},
            "flops": flops,
            "fine_probe_prefix": "bench.probe.level2.",
        }

    def shutdown(self) -> None:
        self.lb.shutdown()


# ---------------------------------------------------------------------------
# the comparison with the plain reference
# ---------------------------------------------------------------------------
class Compared:
    """The rows one run compares, drawn from the seed after the window."""

    def __init__(self, cell: MLDACell, seed: int) -> None:
        picks = cell.tr["check_rows"]
        rng = np.random.default_rng(gen.seed_words(seed).spawn(3)[2])
        self.rows = {}
        for lvl in LEVELS:
            k = int(picks.get(f"level{lvl}", 0))
            thetas, outs = cell.taps[lvl].rows()
            if k:  # none served leaves the rows empty: compared as a failure
                idx = rng.choice(len(thetas), size=min(k, len(thetas)), replace=False)
                self.rows[lvl] = (thetas[idx], outs[idx])
        k = int(picks.get("level1_logp", 0))
        self.logp = None
        if k:
            steps = cell.proposals or [(np.zeros((0, 2)), np.zeros(0), np.zeros(0, bool))]
            psi = np.concatenate([p for p, _, _ in steps])
            lp = np.concatenate([lp for _, lp, _ in steps])
            idx = np.nonzero(np.concatenate([m for _, _, m in steps]))[0]
            idx = rng.choice(idx, size=min(k, len(idx)), replace=False)
            self.logp = (psi[idx], lp[idx])


class Reference:
    """The plain float32 reference (``dtype`` lower for the control, which
    takes the trained GP and the observations of ``share``: only its
    forward solves and its GP cross-covariances run in ``dtype``)."""

    def __init__(self, cfg: Dict[str, Any], dtype=None, share: Optional["Reference"] = None) -> None:
        import jax.numpy as jnp

        from bench.reference import tohoku

        self.mod = tohoku
        self.cfg = cfg
        self.dtype = dtype or jnp.float32
        sc = cfg["scenario"]
        self.fine = tohoku.Forward(sc, cfg["fine_grid"], self.dtype, block=8)
        self.coarse = tohoku.Forward(sc, cfg["coarse_grid"], self.dtype, block=8)
        self._gp = None if share is None else share.gp()
        self._obs = None if share is None else share.obs()

    def gp(self):
        if self._gp is None:
            import jax.numpy as jnp

            cfg = self.cfg
            train = self.mod.Forward(cfg["scenario"], cfg["coarse_grid"], jnp.float32, block=128)
            self._gp = self.mod.GaussianProcess(cfg["scenario"], cfg["gp"], train)
        return self._gp

    def obs(self):
        if self._obs is None:
            import jax.numpy as jnp

            f32 = self.mod.Forward(self.cfg["scenario"], self.cfg["fine_grid"], jnp.float32, block=8)
            self._obs = self.mod.observations(self.cfg["scenario"], f32)
        return self._obs

    def level(self, lvl: int, thetas: np.ndarray) -> np.ndarray:
        if lvl == 0:
            return self.gp()(thetas, dtype=self.dtype)
        return (self.coarse if lvl == 1 else self.fine)(thetas)


def compare(
    cfg: Dict[str, Any], rows: Compared, ref: Reference, control: Optional[Reference] = None
) -> Dict[str, float]:
    """The numbers compared: per level, the widest gap of an observable in
    units of its observation noise; for device-resident proposals, the
    widest relative gap of the coarse log-posterior.  With ``control`` (the
    reference in a lower precision) in the program's place, the same
    numbers for the control, on the same inputs."""
    sigma = ref.mod.noise_sigma(cfg["scenario"])
    sc = cfg["scenario"]
    out = {}
    names = {0: "gp_gap_sigma", 1: "coarse_gap_sigma", 2: "fine_gap_sigma"}
    for lvl, (thetas, got) in rows.rows.items():
        if not len(thetas):
            out[names[lvl]] = float("inf")
            continue
        want = ref.level(lvl, thetas)
        if control is not None:
            got = control.level(lvl, thetas)
        out[names[lvl]] = float(np.max(np.abs(got - want) / sigma))
    if rows.logp is not None and not len(rows.logp[0]):
        out["coarse_logp_gap"] = float("inf")
    elif rows.logp is not None:
        psi, got = rows.logp
        want = ref.mod.log_posterior(sc, ref.obs(), ref.coarse(psi), psi)
        if control is not None:
            got = ref.mod.log_posterior(sc, ref.obs(), control.coarse(psi), psi)
        with np.errstate(invalid="ignore"):
            gap = np.where(
                np.isneginf(got) & np.isneginf(want), 0.0,
                np.abs(got - want) / (1.0 + np.abs(want)),
            )
        gap = np.where(np.isnan(gap), np.inf, gap)
        out["coarse_logp_gap"] = float(np.max(gap))
    return out


def readings(ctx: harness.Context, seeds):
    """For each seed, a short window at the cell's own load and its
    compared numbers, for the program and for the control (the reference
    in bfloat16 in the program's place, on the same inputs); the limits of
    ``correct`` are set from these (``bench/limits.py``)."""
    import dataclasses

    import jax.numpy as jnp

    cfg = ctx.cell.config
    ref = Reference(cfg)
    control = None
    inv = None
    for seed in seeds:
        c = dataclasses.replace(ctx, seed=seed)
        cell = MLDACell(c, inv=inv)
        inv = cell.inv
        win = cell.window(cell.calibrate())
        cell.shutdown()
        rows = Compared(cell, seed)
        del cell
        got = compare(cfg, rows, ref)
        control = control or Reference(cfg, jnp.bfloat16, share=ref)
        low = compare(cfg, rows, ref, control=control)
        yield {"seed": seed, "window_s": win["t1"] - win["t0"], "program": got, "control": low}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run(ctx: harness.Context) -> Outcome:
    import jax

    cell = MLDACell(ctx)
    n = cell.calibrate()
    setup_done = now()
    win = cell.window(n)
    facts = cell.facts(win)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    before, after, result = win["before"], win["after"], win["result"]
    failed = int(after["failures"] - before["failures"]) + len(result.failures)
    attempted = int(after["n_requests"] - before["n_requests"]) + failed
    cell.shutdown()
    rows = Compared(cell, ctx.seed)
    cfg = ctx.cell.config
    del cell  # free the program's state before the reference runs
    t_ref = now()
    got = compare(cfg, rows, Reference(cfg))
    checks = [Check("failed_requests", float(failed), 0.0)]
    checks += [Check(k, v, float(cfg["limits"][k])) for k, v in got.items()]
    window_s = win["t1"] - win["t0"]
    notes = [
        f"[setup] {setup_done - ctx.process_start:.3f} s to the window "
        f"(warm-up segment included); window asks {n} fine samples per chain",
        f"[window] {window_s:.3f} s, {facts['fine_samples']} fine samples, "
        f"{attempted} requests, compiles in window: {win['compiles']} "
        f"({win['compile_s']:.3f} s)",
        f"[reference] {now() - t_ref:.3f} s",
    ]
    return Outcome(
        end_to_end={
            "fine_samples_per_s": facts["fine_samples"] / window_s,
            "setup_s": win["t0"] - ctx.process_start,
        },
        attempted=attempted,
        failed=failed,
        checks=checks,
        memory_peak_bytes=peak,
        facts=facts,
        before=before,
        after=after,
        notes=notes,
    )
