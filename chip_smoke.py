#!/usr/bin/env python3
"""Bring-up check of the main path on a TPU, through the normal entry points.

Run from the root of a checkout (no ``PYTHONPATH`` needed):

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # four chips: the sharded level-2 pool only

One chip runs, in one process and in this order:

* ``device``   — JAX's first device must be a TPU; there is no CPU branch.
* ``kernels``  — the fused and strip SWE kernels at 96² and 288² (B = 8)
  against ``repro.swe.solver.step``, and the Matérn-5/2 kernel at 8×512
  and 512×512 against ``repro.core.gp.matern52``; every compiled program
  must hold a Mosaic kernel (``tpu_custom_call``).
* ``uq-build`` — the ``paper`` preset (96²/288² grids, GP on 512 LHS
  points) built through :func:`repro.swe.inversion.build_inversion`.
* ``uq-forward`` / ``uq-gp`` — the fine and coarse forwards at 4 fixed
  thetas and the GP posterior mean at 16 held-out thetas against the same
  programs on the host's CPU device; padded batched rows against
  per-request rows.
* ``uq-mlda`` / ``uq-device-resident`` — 5 chains through the balancer
  (:func:`repro.swe.inversion.sample_inversion`), step machines and then
  the coupled device-resident mode.
* ``serving`` — qwen2-0.5b at its published widths in bf16: 4 requests
  through ``ServingEngine(mode="paged")``, tokens against
  ``mode="generation"``.

Each phase prints one line: wall time, compile seconds, each compared
quantity with its max error beside its bound, and the device kind.  Every
phase runs even after another failed, so one run reports them all; any
failure makes the exit code non-zero.  Only on success is the last line
of stdout ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time
import traceback
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

# Mosaic-compiled Pallas kernels appear under this name in the TPU HLO.
KERNEL_MARK = "tpu_custom_call"
# Bounds.  Kernel vs oracle: the repo's own kernel-test bounds
# (tests/test_kernels.py) — max |err| / max(|h|, 1) for the SWE depth,
# absolute for the Matérn matrix (values in [0, 1]).  Momentum gets the
# resolution of fp32 at ocean depth instead: one ulp of h (~5e-4 m at
# 7 km) moves hu by g·h·ulp(h)/dx·dt (~0.1 m²/s) through the well-balanced
# pressure term in one step, and two compilers round h differently now and
# then.  The bound allows one such ulp per step.
SWE_REL_TOL = 1e-5
MATERN_ABS_TOL = 5e-6
# Chip vs host CPU, batched vs per-request and four chips vs one, on the
# observables (hmax_1, t_arr_1, hmax_2, t_arr_2): a tenth of the
# measurement noise the likelihood assumes (TohokuInverseProblem), so no
# difference here can move the posterior by a noticeable amount.
OBS_NOISE = np.array([0.04, 0.012, 0.04, 0.012])
OBS_TOL = 0.1 * OBS_NOISE

KERNEL_STEPS = 10
KERNEL_BATCH = 8
KERNEL_GRIDS = (96, 288)
FIXED_THETAS = ((0.0, 0.0), (100.0, -50.0), (-120.0, 80.0), (60.0, 150.0))
EXTRA_THETAS = ((-60.0, -140.0), (150.0, 20.0))  # 6 rows pad to B = 8
N_HELDOUT = 16
N_CHAINS = 5
N_FINE_SAMPLES = 8
SERVE_ARCH = "qwen2-0.5b"
SERVE_PROMPT_LEN = 32
SERVE_NEW = (8, 16, 24, 32)


class Failed(Exception):
    """A phase's comparison or invariant did not hold."""


@dataclasses.dataclass
class Check:
    label: str
    err: float
    bound: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.err)) and self.err <= self.bound

    def __str__(self) -> str:
        rel = "<=" if self.ok else "EXCEEDS"
        return f"{self.label} err={self.err:.3e} {rel} {self.bound:.1e}"


@dataclasses.dataclass
class Fact:
    """An invariant that must hold (``ok``) or a value shown for information."""

    label: str
    value: object
    ok: bool = True

    def __str__(self) -> str:
        return f"{self.label}={self.value}" + ("" if self.ok else " FAILED")


class CompileClock:
    """Seconds JAX spent compiling (or loading from the persistent cache)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.seconds = 0.0
        self._lock = threading.Lock()

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            with self._lock:
                self.seconds += duration


class Smoke:
    def __init__(self, jax, kind: str) -> None:
        self.kind = kind
        self.failed: list = []
        self.clock = CompileClock()
        jax.monitoring.register_event_duration_secs_listener(self.clock)

    def run(self, name: str, fn, *args):
        """Run one phase, print its line; returns the phase's value or None."""
        c0, t0 = self.clock.seconds, time.monotonic()
        items, value, ok = [], None, True
        try:
            out = fn(*args)
            items, value = (out if isinstance(out, tuple) else (out, None))
            ok = all(it.ok for it in items)
        except Exception as e:  # report the phase, carry on with the next
            traceback.print_exc()
            items.append(Fact("error", f"{type(e).__name__}: {e}", ok=False))
            ok = False
        wall = time.monotonic() - t0
        print(
            f"[{name}] {'ok' if ok else 'FAIL'} wall={wall:.1f}s "
            f"compile={self.clock.seconds - c0:.1f}s | "
            + "; ".join(str(it) for it in items)
            + f" | device_kind={self.kind}",
            flush=True,
        )
        if not ok:
            self.failed.append(name)
        return value


def max_abs(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))))


def max_rel(a, b) -> float:
    return max_abs(a, b) / max(float(np.max(np.abs(np.asarray(b, np.float64)))), 1.0)


def momentum_resolution(cfg, h_max: float, dt: float) -> float:
    """Change of hu in one step from one fp32 ulp of depth at ``h_max``."""
    ulp = float(np.spacing(np.float32(h_max)))
    return cfg.g * h_max * ulp / min(cfg.dx, cfg.dy) * dt


def obs_err(a, b) -> float:
    """Max over rows and observables of |a - b| in units of ``OBS_TOL``
    (so the bound is 1)."""
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return float(np.max(d / OBS_TOL))


def compiled(jax, fn, *args):
    """AOT-compile ``fn`` and require a Mosaic kernel in the program."""
    exe = jax.jit(fn).lower(*args).compile()
    if KERNEL_MARK not in exe.as_text():
        raise Failed(f"no {KERNEL_MARK} in the compiled program of {fn}")
    return exe


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_device(jax):
    devs = jax.devices()
    return [
        Fact("platform", devs[0].platform, ok=devs[0].platform == "tpu"),
        Fact("count", len(devs)),
    ]


def phase_kernels(jax, grids=KERNEL_GRIDS, batch=KERNEL_BATCH, steps=KERNEL_STEPS):
    import jax.numpy as jnp

    from repro.core.gp import GPParams, matern52 as matern_oracle
    from repro.kernels.matern.ops import matern52 as matern_kernel
    from repro.kernels.swe_flux.ops import swe_step_batched
    from repro.swe import TohokuScenario
    from repro.swe.solver import SWEState, stable_dt, step

    items = []
    for n in grids:
        sc = TohokuScenario(nx=n, ny=n)
        cfg, b = sc.cfg, sc.bathymetry()
        h_rest = jnp.maximum(-b, 0.0)
        thetas = jnp.linspace(-150.0, 150.0, 2 * batch).reshape(batch, 2)
        h0 = jax.vmap(lambda t: jnp.maximum(h_rest + sc.displacement(t), 0.0))(thetas)
        state = SWEState(h0, jnp.zeros_like(h0), jnp.zeros_like(h0))
        dt = stable_dt(cfg, float(jnp.max(h_rest)))

        def advance(step_fn):
            return lambda s: jax.lax.fori_loop(0, steps, lambda _, x: step_fn(x), s)

        ref = jax.jit(advance(jax.vmap(lambda s: step(s, b, cfg, dt))))(state)
        for fused in (True, False):
            exe = compiled(jax, advance(
                lambda s, f=fused: swe_step_batched(s, b, dt, cfg=cfg, fused=f)
            ), state)
            out = exe(state)
            name = f"swe_{'fused' if fused else 'strip'}_{n}"
            items.append(Check(f"{name}_h", max_rel(out.h, ref.h), SWE_REL_TOL))
            items.append(Check(
                f"{name}_hu_hv_m2/s",
                max(max_abs(out.hu, ref.hu), max_abs(out.hv, ref.hv)),
                steps * momentum_resolution(cfg, float(jnp.max(ref.h)), dt),
            ))

    cpu = jax.devices("cpu")[0]
    params = GPParams(
        log_lengthscales=jnp.log(jnp.array([80.0, 120.0])),
        log_outputscale=jnp.zeros(()),
        log_noise=jnp.log(jnp.asarray(1e-2)),
    )
    rng = np.random.default_rng(0)
    x = rng.uniform(-200.0, 200.0, (512, 2)).astype(np.float32)
    for m in (8, 512):
        a = jnp.asarray(x[:m])
        xb = jnp.asarray(x)
        exe = compiled(jax, matern_kernel, a, xb, params)
        got = exe(a, xb, params)
        want = matern_oracle(*jax.device_put((a, xb, params), cpu))
        xla = matern_oracle(a, xb, params)  # the oracle itself, on the chip
        items.append(Check(f"matern_{m}x512", max_abs(got, want), MATERN_ABS_TOL))
        items.append(Check(f"matern_xla_{m}x512_vs_cpu", max_abs(xla, want), MATERN_ABS_TOL))
    return items


def phase_uq_build(jax, w):
    from repro.swe.inversion import build_inversion

    inv = build_inversion(w)
    y_ok = bool(np.all(np.isfinite(inv.problem.y_obs)))
    n_gp = int(inv.gp.x_train.shape[0])
    items = [
        Fact("preset", w.name),
        Fact("grids", f"{w.coarse_grid}/{w.fine_grid}"),
        Fact("fine_steps", inv.hierarchy["forward_fine_batch"].n_steps),
        Fact("gp_points", n_gp, ok=n_gp == w.gp_train_points),
        Fact("gp_train_s", round(inv.gp_seconds, 1)),
        Fact("y_obs_finite", y_ok, ok=y_ok),
    ]
    return items, inv


def phase_uq_forward(jax, inv):
    import jax.numpy as jnp

    cpu = jax.devices("cpu")[0]
    h = inv.hierarchy
    items = []
    fixed = np.asarray(FIXED_THETAS, np.float32)
    six = np.concatenate([fixed, np.asarray(EXTRA_THETAS, np.float32)])
    for level, scenario in (("fine", inv.fine), ("coarse", inv.coarse)):
        single, batched = h[f"forward_{level}"], h[f"forward_{level}_batch"]
        chip = np.stack([np.asarray(single(jnp.asarray(t))) for t in six])
        with jax.default_device(cpu):
            f_cpu = jax.jit(scenario.build_forward())
            host = np.stack([np.asarray(f_cpu(jnp.asarray(t))) for t in fixed])
        items.append(Check(f"{level}_chip_vs_cpu/obs_tol", obs_err(chip[:4], host), 1.0))
        rows = np.asarray(batched(jnp.asarray(six)))
        items.append(Check(f"{level}_batch8_vs_single/obs_tol", obs_err(rows, chip), 1.0))
        items.append(Fact(f"{level}_batch_bit_identical", bool(np.array_equal(rows, chip))))
        items.append(Fact(f"{level}_finite", bool(np.all(np.isfinite(chip))),
                          ok=bool(np.all(np.isfinite(chip)))))
    return items


def phase_uq_gp(jax, inv):
    import jax.numpy as jnp

    from repro.core.gp import fit_gp

    cpu = jax.devices("cpu")[0]
    gp, w = inv.gp, inv.workload
    lo, hi = inv.problem.prior_bounds()
    xq = np.random.default_rng(1).uniform(lo, hi, (N_HELDOUT, 2)).astype(np.float32)
    chip = np.asarray(gp.predict(jnp.asarray(xq)))
    fields = ("x_train", "y_train", "y_mean", "y_scale", "params", "chol", "alpha")
    gp_host = dataclasses.replace(
        gp, **{f: jax.device_put(getattr(gp, f), cpu) for f in fields}
    )
    with jax.default_device(cpu):
        xq_cpu = jnp.asarray(xq)
        same_gp = np.asarray(gp_host.predict(xq_cpu))
        refit = fit_gp(gp_host.x_train, gp_host.y_train, steps=w.gp_opt_steps)
        refit_mean = np.asarray(refit.predict(xq_cpu))
    rows = np.asarray(gp.batch_call(jnp.asarray(xq[:8])))
    single = np.stack([np.asarray(gp(jnp.asarray(t))) for t in xq[:8]])
    return [
        Check("gp_mean_chip_vs_cpu/obs_tol", obs_err(chip, same_gp), 1.0),
        Check("gp_trained_chip_vs_cpu/obs_tol", obs_err(chip, refit_mean), 1.0),
        Check("gp_batch8_vs_single/obs_tol", obs_err(rows, single), 1.0),
        Fact("gp_batch_bit_identical", bool(np.array_equal(rows, single))),
    ]


def phase_uq_mlda(jax, inv, n_chains=N_CHAINS, n_fine=N_FINE_SAMPLES):
    from repro.swe.inversion import sample_inversion

    w = inv.workload
    run = sample_inversion(inv, n_chains=n_chains, policy=w.balancer_policy, n_fine_samples=n_fine)
    chains = run.result.chains
    lo, hi = inv.problem.prior_bounds()
    mean = chains.reshape(-1, chains.shape[-1]).mean(0)
    inside = bool(np.all(np.isfinite(mean)) and np.all(mean >= lo) and np.all(mean <= hi))
    s = run.summary
    return [
        Fact("chains_done", f"{chains.shape[0]}/{n_chains}", ok=chains.shape[0] == n_chains),
        Fact("samples_per_chain", chains.shape[1], ok=chains.shape[1] == n_fine),
        Fact("chain_failures", len(run.result.failures), ok=not run.result.failures),
        Fact("failed_requests", s["failures"], ok=s["failures"] == 0),
        Fact("posterior_mean_km", np.round(mean.astype(float), 2).tolist(), ok=inside),
        Fact("leaked_threads", run.leaked_threads, ok=run.leaked_threads <= 0),
        Fact("requests", s["n_requests"]),
        Fact("mean_idle_ms", round(s["mean_idle_s"] * 1e3, 3)),
        Fact("sampling_wall_s", round(run.wall_s, 2)),
        Fact("batch_histogram", s["batch_histogram"]),
    ]


def phase_serving(jax, arch=SERVE_ARCH, prompt_len=SERVE_PROMPT_LEN, n_new=SERVE_NEW, cfg=None):
    import jax.numpy as jnp

    from repro.configs import ARCHS
    from repro.runtime.serve_loop import ServingEngine

    cfg = cfg or ARCHS[arch]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=(1, prompt_len)) for _ in n_new]
    tokens, items = {}, []
    for mode in ("paged", "generation"):
        with ServingEngine({arch: cfg}, mode=mode, n_slots=len(n_new)) as eng:
            gens = [eng.submit(arch, p, k) for p, k in zip(prompts, n_new)]
            tokens[mode] = [np.asarray(g.result(timeout=600).tokens) for g in gens]
            if mode == "paged":
                bundle, params = eng.bundles[arch], eng.params[arch]
                pf = jax.jit(bundle.prefill_state, static_argnums=(2,))
                finite = all(
                    bool(jnp.all(jnp.isfinite(pf(params, jnp.asarray(p, jnp.int32), eng.cache_len)[0])))
                    for p in prompts
                )
                n_params = sum(x.size for x in jax.tree.leaves(params))
                items += [
                    Fact("widths", f"L{cfg.n_layers}/d{cfg.d_model}/V{cfg.vocab}"),
                    Fact("params_M", round(n_params / 1e6, 1)),
                    Fact("dtype", str(jax.tree.leaves(params)[0].dtype)),
                    Fact("finite_logits", finite, ok=finite),
                ]
    counts = [len(t) for t in tokens["paged"]]
    agree = np.mean([
        np.mean(a[: min(len(a), len(b))] == b[: min(len(a), len(b))])
        for a, b in zip(tokens["paged"], tokens["generation"])
    ])
    items += [
        Fact("token_counts", counts, ok=counts == list(n_new)),
        Fact("paged_vs_generation_token_agreement", round(float(agree), 4)),
    ]
    return items


def phase_four_chips(jax, w, n_devices=4, batch=KERNEL_BATCH):
    import jax.numpy as jnp

    from repro.balancer import ShardedBatchServer
    from repro.runtime.sharding import data_mesh, data_policy
    from repro.swe import TohokuScenario

    devs = jax.devices()
    if len(devs) < n_devices:
        raise Failed(f"--four-chips needs {n_devices} devices, JAX sees {len(devs)}")
    fine = TohokuScenario(nx=w.fine_grid[0], ny=w.fine_grid[1], t_end=w.t_end_s)
    thetas = np.random.default_rng(2).uniform(-150.0, 150.0, (batch, 2)).astype(np.float32)
    pool = ShardedBatchServer(
        fine.build_stacked_forward(), data_policy(data_mesh(n_devices)),
        name="fine-pool", capacity_tags=("level2",), max_batch=w.max_batch,
        cache_key=("pool", "level2"),
    )
    sharded = np.stack(pool.batch_call(list(thetas)))
    (exe,) = pool.executables.values()
    out = exe(jnp.asarray(thetas))
    placement = sorted(str(s.device.id) for s in out.addressable_shards)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs[:n_devices]]
    one_chip = np.asarray(fine.build_batch_forward()(jnp.asarray(thetas)))
    used = sorted({s.device.id for s in out.addressable_shards})
    return [
        Fact("mesh", f"data:{n_devices}"),
        Fact("batch", batch),
        Fact("output_shard_devices", ",".join(placement), ok=len(used) == n_devices),
        Fact("peak_bytes_per_device", peaks),
        Check("sharded_vs_one_chip/obs_tol", obs_err(sharded, one_chip), 1.0),
        Fact("bit_identical", bool(np.array_equal(sharded, one_chip))),
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run the paper-preset level-2 pool sharded over four chips "
        "against one chip, and nothing else",
    )
    args = ap.parse_args(argv)
    # The comparisons need the host's CPU backend next to the chip.
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    sys.path.insert(0, str(SRC))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX's first device is a {dev.platform} "
            "device); this check runs only on a TPU",
            file=sys.stderr,
        )
        return 2

    from repro.configs.tohoku_mlda import PAPER
    from repro.launch.compile_cache import cache_entries, enable_compile_cache

    cache = enable_compile_cache()
    smoke = Smoke(jax, dev.device_kind)
    smoke.run("device", phase_device, jax)
    if args.four_chips:
        smoke.run("four-chips", phase_four_chips, jax, PAPER)
    else:
        smoke.run("kernels", phase_kernels, jax)
        inv = smoke.run("uq-build", phase_uq_build, jax, PAPER)
        if inv is not None:
            smoke.run("uq-forward", phase_uq_forward, jax, inv)
            smoke.run("uq-gp", phase_uq_gp, jax, inv)
            smoke.run("uq-mlda", phase_uq_mlda, jax, inv)
            resident = dataclasses.replace(
                inv, workload=dataclasses.replace(PAPER, device_resident=True)
            )
            smoke.run("uq-device-resident", phase_uq_mlda, jax, resident)
        smoke.run("serving", phase_serving, jax)
    print(f"[cache] {cache} holds {cache_entries(cache)} entries", flush=True)
    if smoke.failed:
        print(f"chip_smoke: FAILED phases: {', '.join(smoke.failed)}", file=sys.stderr)
        return 1
    devs = jax.devices()
    print(json.dumps({
        "ok": True,
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
