"""Coalesced-dispatch semantics (DESIGN.md §2: batched forward-solve engine).

Four layers:

1. engine semantics through the threaded dispatcher: stacked
   ``BatchServer`` dispatch, bit-identical batched vs sequential results,
   per-member error isolation (one poisoned theta fails only its own
   request), adaptive coalescing window, batch-size telemetry;
2. a deterministic **fake-clock harness** for FIFO fairness under
   batching: coalescing drains same-tag batchable peers in arrival order
   and never reorders the rest of the queue;
3. batched solver factories: SWE ``make_solver(batch=True)`` /
   ``TohokuScenario.build_batch_forward`` AOT executables and the GP
   ``batch_call`` path are bit-identical (fp32) to per-request
   evaluation, executables cached per power-of-two batch size;
4. the ensemble path: an N-chain run over ``BatchServer`` pools draws
   bit-identical chains to per-request dispatch while coalescing fires.
"""
from __future__ import annotations

import threading
import time
from collections import deque

import numpy as np
import pytest

from repro.balancer import BatchServer, LoadBalancer, Server


# ---------------------------------------------------------------------------
# 1. engine semantics (threaded dispatcher)
# ---------------------------------------------------------------------------
def test_batch_server_single_and_stacked_results_identical():
    """One server, same thetas: coalesced dispatch must return exactly what
    sequential per-request dispatch returns, in submission order."""
    calls = []

    def batch_fn(stacked):  # (B, 3) -> (B, 3)
        calls.append(stacked.shape[0])
        time.sleep(0.005)  # long enough for later submits to queue up
        return np.sin(stacked) + stacked**2

    thetas = [np.full(3, 0.1 * i) for i in range(10)]

    lb_seq = LoadBalancer([BatchServer(batch_fn)])  # no window: singles
    seq = [lb_seq.submit(t, tag="gp", batchable=True) for t in thetas]
    lb_seq.shutdown()
    assert set(calls) == {1}

    calls.clear()
    lb = LoadBalancer([BatchServer(batch_fn)], batch_window_s=0.02)
    reqs = [lb.submit_async(t, tag="gp", batchable=True) for t in thetas]
    got = [lb.result(r) for r in reqs]
    lb.shutdown()
    assert max(calls) > 1, "no coalescing fired"
    for a, b in zip(seq, got):
        assert np.array_equal(a, b)


def test_per_member_error_isolation_nan_theta():
    """check_finite: a NaN member poisons only its own request — its batch
    mates complete normally and the server stays alive."""
    release = threading.Event()

    def batch_fn(stacked):
        release.wait(5)
        return stacked * 2.0

    srv = BatchServer(batch_fn, check_finite=True, name="b0")
    lb = LoadBalancer([srv], batch_window_s=0.01)
    good0 = lb.submit_async(np.array([1.0]), tag="t", batchable=True)
    time.sleep(0.03)  # good0 dispatches alone and parks on `release`
    bad = lb.submit_async(np.array([np.nan]), tag="t", batchable=True)
    good1 = lb.submit_async(np.array([3.0]), tag="t", batchable=True)
    release.set()
    assert np.array_equal(lb.result(good0), [2.0])
    assert np.array_equal(lb.result(good1), [6.0])
    with pytest.raises(FloatingPointError, match="batch member"):
        lb.result(bad)
    assert not srv.dead, "member failure must not kill the server"
    assert lb.submit(np.array([5.0]), tag="t", batchable=True)[0] == 10.0
    # Same semantics when the poisoned request is NOT coalesced (lone
    # request, or batchable=False): fails alone, server survives.
    for batchable in (True, False):
        with pytest.raises(FloatingPointError, match="batch member"):
            lb.submit(np.array([np.nan]), tag="t", batchable=batchable)
    assert not srv.dead
    # poisoned thetas are booked as failures, not served work
    assert lb.summary()["failures"] == 3
    lb.shutdown()


def test_exception_members_scatter_without_server_death():
    """A legacy list-contract batch_fn may return Exception entries; they
    fail their member only."""
    def batch_fn(thetas):
        return [
            ValueError(f"bad {t}") if t < 0 else t * 10 for t in thetas
        ]

    lb = LoadBalancer(
        [Server(lambda t: t * 10, batch_fn=batch_fn)], batch_window_s=0.01
    )
    reqs = [lb.submit_async(t, tag="x", batchable=True) for t in (1, -2, 3)]
    results = []
    for r in reqs:
        try:
            results.append(lb.result(r))
        except ValueError as e:
            results.append(str(e))
    assert results == [10, "bad -2", 30]
    assert all(not s.dead for s in lb.servers)
    lb.shutdown()


def test_whole_batch_failure_retries_members():
    """A whole-call exception still follows the server-death path: members
    retry on the surviving server."""
    def broken(thetas):
        raise RuntimeError("kaboom")

    ok = Server(lambda t: t + 1, batch_fn=lambda ts: [t + 1 for t in ts],
                name="ok")
    lb = LoadBalancer(
        [Server(lambda t: t + 1, batch_fn=broken, name="bad"), ok],
        batch_window_s=0.01,
    )
    # force the bad server to take the first dispatch
    reqs = [lb.submit_async(i, tag="x", batchable=True) for i in range(6)]
    assert sorted(lb.result(r) for r in reqs) == [1, 2, 3, 4, 5, 6]
    lb.shutdown()


def test_adaptive_window_shrinks_with_ewma():
    """The coalescing window is a fraction of the tag's EWMA service time,
    capped by batch_window_s."""
    lb = LoadBalancer(
        [BatchServer(lambda ts: ts)], batch_window_s=0.5, batch_window_frac=0.25
    )
    assert lb._coalesce_window("t") == 0.5  # no data yet: full cap
    lb._telemetry._record_runtime_locked("t", 0.02, "s0")
    assert lb._coalesce_window("t") == pytest.approx(0.005)
    lb._telemetry._record_runtime_locked("slow", 10.0, "s0")
    assert lb._coalesce_window("slow") == 0.5  # cap binds for long solves
    lb.shutdown()


def test_batch_histogram_telemetry():
    def batch_fn(ts):
        time.sleep(0.005)
        return ts * 2

    lb = LoadBalancer([BatchServer(batch_fn)], batch_window_s=0.02)
    reqs = [lb.submit_async(np.array([i]), tag="gp", batchable=True)
            for i in range(8)]
    for r in reqs:
        lb.result(r)
    hist = lb.telemetry.batch_histogram("gp")
    assert sum(size * n for size, n in hist.items()) == 8
    assert lb.summary()["batch_histogram"]["gp"] == hist
    assert lb.telemetry.batch_histogram() == {"gp": hist}
    lb.shutdown()


def test_full_batch_fires_early_without_waiting_out_window():
    """Non-blocking coalescing window: the worker parks on an event with
    deadline = window and is fired the moment the ``max_batch``-th same-tag
    member arrives — a full batch never sleeps out the window."""
    window = 1.0
    calls = []
    park = threading.Event()
    first = threading.Event()

    def batch_fn(stacked):
        if not first.is_set():
            first.set()
            park.wait(5)
        calls.append(stacked.shape[0])
        return stacked * 2.0

    lb = LoadBalancer(
        [BatchServer(batch_fn, max_batch=4)],
        batch_window_s=window, batch_window_frac=100.0, max_batch=4,
    )
    warm = lb.submit_async(np.array([0.0]), tag="t", batchable=True)
    time.sleep(0.05)  # warm parks the server
    reqs = [lb.submit_async(np.array([float(i)]), tag="t", batchable=True)
            for i in (1, 2)]
    t0 = time.monotonic()
    park.set()  # warm completes; the next dispatch arms the window (1 peer
    time.sleep(0.15)  # queued < max_batch - 1), and the worker parks in it
    reqs += [lb.submit_async(np.array([float(i)]), tag="t", batchable=True)
             for i in (3, 4)]  # the max_batch-th member fires the waiter
    for r in [warm] + reqs:
        lb.result(r, timeout=5)
    elapsed = time.monotonic() - t0
    assert 4 in calls, f"full batch did not coalesce: {calls}"
    assert elapsed < 0.6 * window, (
        f"batch waited out the window ({elapsed:.2f}s >= {window}s)"
    )
    lb.shutdown()


def test_already_full_batch_pays_no_window_at_dispatch():
    """A queue already holding >= max_batch same-tag members dispatches the
    batch immediately — the window is never armed."""
    window = 1.0
    calls = []
    park = threading.Event()
    first = threading.Event()

    def batch_fn(stacked):
        if not first.is_set():
            first.set()
            park.wait(5)
        calls.append(stacked.shape[0])
        return stacked

    lb = LoadBalancer(
        [BatchServer(batch_fn, max_batch=3)],
        batch_window_s=window, batch_window_frac=100.0,
    )
    warm = lb.submit_async(np.array([0.0]), tag="t", batchable=True)
    time.sleep(0.05)
    reqs = [lb.submit_async(np.array([float(i)]), tag="t", batchable=True)
            for i in range(1, 4)]  # full batch + spare already queued
    t0 = time.monotonic()
    park.set()
    for r in [warm] + reqs:
        lb.result(r, timeout=5)
    assert time.monotonic() - t0 < 0.5 * window, "paid the window when full"
    assert 3 in calls
    lb.shutdown()


def test_lone_batchable_request_pays_zero_window_batchserver():
    """A lone batchable request on a BatchServer executes immediately —
    there is nothing to coalesce, so the window is never armed."""
    window = 0.5
    lb = LoadBalancer([BatchServer(lambda st: st * 2.0)],
                      batch_window_s=window)
    t0 = time.monotonic()
    assert lb.submit(np.array([3.0]), tag="t", batchable=True)[0] == 6.0
    assert time.monotonic() - t0 < window / 2, "lone request paid the window"
    assert lb.telemetry.batch_histogram("t") == {1: 1}
    lb.shutdown()


def test_server_max_batch_caps_coalescing():
    sizes = []

    def batch_fn(stacked):
        sizes.append(stacked.shape[0])
        time.sleep(0.01)
        return stacked

    lb = LoadBalancer(
        [BatchServer(batch_fn, max_batch=2)], batch_window_s=0.02, max_batch=64
    )
    reqs = [lb.submit_async(np.array([i]), tag="t", batchable=True)
            for i in range(9)]
    for r in reqs:
        lb.result(r)
    assert max(sizes) <= 2
    lb.shutdown()


# ---------------------------------------------------------------------------
# 2. FIFO fairness under batching (fake clock — no threads, no sleeps)
# ---------------------------------------------------------------------------
def simulate_batched(arrivals, *, n_servers=1, max_batch=8, service_time=1.0):
    """Drive the coalescing drain rule on a simulated clock.

    ``arrivals`` is ``[(t, tag, batchable), ...]``.  Mirrors the
    dispatcher: the FIFO head dispatches when a server frees; a batchable
    head then drains queued same-tag batchable peers in arrival order (up
    to ``max_batch``), leaving everyone else's relative order untouched.
    Returns the dispatch log ``[(t, server, [request indices]), ...]``.
    """
    queue: deque = deque()
    log = []
    free_at = [0.0] * n_servers
    arrivals = sorted(enumerate(arrivals), key=lambda e: e[1][0])
    i = 0
    t = 0.0
    while i < len(arrivals) or queue:
        if not queue:  # jump to next arrival
            t = max(t, arrivals[i][1][0])
        while i < len(arrivals) and arrivals[i][1][0] <= t:
            idx, (at, tag, batchable) = arrivals[i]
            queue.append((idx, tag, batchable))
            i += 1
        s = min(range(n_servers), key=lambda k: free_at[k])
        t = max(t, free_at[s])
        # late arrivals may have landed while the server was busy
        while i < len(arrivals) and arrivals[i][1][0] <= t:
            idx, (at, tag, batchable) = arrivals[i]
            queue.append((idx, tag, batchable))
            i += 1
        if not queue:
            continue
        head = queue.popleft()
        members = [head]
        if head[2]:  # batchable: drain same-tag batchable peers FIFO
            keep = deque()
            while queue and len(members) < max_batch:
                r = queue.popleft()
                if r[2] and r[1] == head[1]:
                    members.append(r)
                else:
                    keep.append(r)
            while keep:
                queue.appendleft(keep.pop())
        log.append((t, s, [m[0] for m in members]))
        free_at[s] = t + service_time
    return log


def test_fifo_fairness_preserved_under_batching():
    """Per-tag dispatch order stays FIFO, batch members are the earliest
    same-tag arrivals, and non-batchable tags are never overtaken within
    their own tag by coalescing."""
    arrivals = []
    for k in range(24):
        tag = ("gp", "pde", "solo")[k % 3]
        arrivals.append((0.1 * k, tag, tag != "solo"))
    log = simulate_batched(arrivals, n_servers=2, max_batch=4)

    dispatched_order = [idx for _, _, members in log for idx in members]
    assert sorted(dispatched_order) == list(range(24)), "lost/dup requests"
    by_tag = {}
    for t, s, members in log:
        tags = {arrivals[m][1] for m in members}
        assert len(tags) == 1, "batch mixed tags"
        by_tag.setdefault(tags.pop(), []).append(members)
    for tag, groups in by_tag.items():
        flat = [m for g in groups for m in g]
        assert flat == sorted(flat), f"tag '{tag}' dispatched out of order"
    # batches formed at all, and solo (non-batchable) never coalesced
    assert any(len(g) > 1 for g in by_tag["gp"] + by_tag["pde"])
    assert all(len(g) == 1 for g in by_tag["solo"])


def test_threaded_fifo_order_within_tag_under_batching():
    """Engine-level check of the same invariant: member indices of every
    realised batch are contiguous-in-arrival-order for their tag."""
    seen = []
    release = threading.Event()

    def batch_fn(stacked):
        release.wait(5)
        time.sleep(0.005)
        seen.append([int(x) for x in stacked[:, 0]])
        return stacked

    lb = LoadBalancer([BatchServer(batch_fn)], batch_window_s=0.01,
                      max_batch=4)
    reqs = [lb.submit_async(np.array([i]), tag="t", batchable=True)
            for i in range(12)]
    release.set()
    for r in reqs:
        lb.result(r)
    lb.shutdown()
    flat = [i for batch in seen for i in batch]
    assert flat == sorted(flat), f"dispatch reordered within tag: {seen}"


# ---------------------------------------------------------------------------
# 3. batched solver factories: bit-identity + executable cache
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def small_scenario():
    from repro.swe import TohokuScenario

    return TohokuScenario(nx=24, ny=24, t_end=900.0)


def test_swe_batched_solver_bit_identical(small_scenario):
    import jax
    import jax.numpy as jnp
    from repro.swe.solver import make_solver

    sc = small_scenario
    cfg, b, probes = sc.cfg, sc.bathymetry(), sc.probe_indices()
    single = jax.jit(make_solver(cfg, b, probes))
    batched = make_solver(cfg, b, probes, batch=True)
    thetas = jnp.asarray([[0.0, 0.0], [60.0, -40.0], [-90.0, 15.0]])
    etas = jnp.stack([sc.displacement(t) for t in thetas])
    series_b, final_b = batched(etas)
    for k in range(3):
        series_1, final_1 = single(etas[k])
        assert np.array_equal(np.asarray(series_1), np.asarray(series_b[k]))
        assert np.array_equal(np.asarray(final_1.h), np.asarray(final_b.h[k]))
    # pow2 padding + per-size executable cache
    assert list(batched.executables) == [(24, 24, 4)]
    batched(etas[:2])  # B=2 is its own pow2 bucket
    assert (24, 24, 2) in batched.executables
    batched(jnp.concatenate([etas, etas[:2]]))  # B=5 pads to 8
    assert (24, 24, 8) in batched.executables


def test_scenario_batch_forward_bit_identical(small_scenario):
    import jax
    import jax.numpy as jnp

    sc = small_scenario
    single = jax.jit(sc.build_forward())
    batched = sc.build_batch_forward()
    thetas = jnp.asarray([[0.0, 0.0], [60.0, -40.0], [-90.0, 15.0]])
    got = np.asarray(batched(thetas))
    want = np.stack([np.asarray(single(t)) for t in thetas])
    assert np.array_equal(want, got)


def test_gp_batch_call_bit_identical():
    import jax.numpy as jnp
    from repro.core.gp import fit_gp

    rng = np.random.default_rng(0)
    x = rng.uniform(-1, 1, (48, 2))
    y = np.stack([np.sin(x[:, 0]), x[:, 0] * x[:, 1]], axis=1)
    gp = fit_gp(x, y, steps=20)
    thetas = rng.uniform(-1, 1, (6, 2))
    want = np.stack([np.asarray(gp(jnp.asarray(t))) for t in thetas])
    got = np.asarray(gp.batch_call(jnp.asarray(thetas)))
    assert np.array_equal(want, got)


@pytest.fixture(scope="module")
def small_gp():
    from repro.core.gp import fit_gp

    rng = np.random.default_rng(1)
    x = rng.uniform(-1, 1, (48, 2))
    y = np.stack([np.sin(x[:, 0]), x[:, 0] * x[:, 1]], axis=1)
    return fit_gp(x, y, steps=20), rng.uniform(-1, 1, (8, 2))


def _eager_posterior_mean(gp, thetas):
    """The level-0 mean as it evaluated before it was compiled: op by op."""
    import jax.numpy as jnp
    from repro.core.gp import matern52

    ks = matern52(jnp.asarray(thetas), gp.x_train, gp.params)
    terms = ks[:, :, None] * gp.alpha[None, :, :]
    mean = jnp.sum(terms, axis=1) * gp.y_scale + gp.y_mean
    # Sum of |terms|: the scale of the cancelling contraction, which sets
    # how far a different rounding of the same formula can move the mean.
    size = jnp.sum(jnp.abs(terms), axis=1) * gp.y_scale + jnp.abs(gp.y_mean)
    return np.asarray(mean), np.asarray(size)


@pytest.mark.parametrize("b", [1, 2, 3, 5, 8])
def test_gp_batch_rows_match_per_row_and_eager_formula(small_gp, b):
    """The compiled level-0 call: every row of a B-row call equals the
    one-row call bit for bit, and equals the eager formula up to float32
    rounding (the compiled program adds in a fixed tree order, and XLA
    may contract a multiply-add where the eager ops rounded twice)."""
    import jax.numpy as jnp

    gp, thetas = small_gp
    thetas = thetas[:b]
    got = np.asarray(gp.batch_call(jnp.asarray(thetas)))
    per_row = np.stack([np.asarray(gp(jnp.asarray(t))) for t in thetas])
    assert got.shape == (b, 2) and got.dtype == np.float32
    assert np.array_equal(got, per_row)
    want, size = _eager_posterior_mean(gp, thetas)
    assert np.all(np.abs(got - want) <= 16 * np.finfo(np.float32).eps * size)


def test_batched_pallas_step_matches_reference(small_scenario):
    """Fused (no-transpose) and strip (batch grid axis) kernels vs the
    pure-jnp oracle, fp32 tolerance as in test_kernels."""
    import jax.numpy as jnp
    from repro.kernels.swe_flux.ops import swe_step_batched
    from repro.swe.solver import SWEState, stable_dt, step as ref_step

    sc = small_scenario
    cfg, b = sc.cfg, sc.bathymetry()
    thetas = [jnp.asarray(t) for t in ([0.0, 0.0], [60.0, -40.0])]
    h0 = jnp.stack([
        jnp.maximum(jnp.maximum(-b, 0.0) + sc.displacement(t), 0.0)
        for t in thetas
    ])
    dt = stable_dt(cfg, float(h0.max()))
    refs = [SWEState(h0[k], jnp.zeros_like(h0[k]), jnp.zeros_like(h0[k]))
            for k in range(2)]
    for variant in ("fused", "strip"):
        st = SWEState(h0, jnp.zeros_like(h0), jnp.zeros_like(h0))
        rr = list(refs)
        for _ in range(3):
            st = swe_step_batched(st, b, dt, cfg=cfg,
                                  fused=variant == "fused")
            rr = [ref_step(s, b, cfg, dt) for s in rr]
        for k in range(2):
            for a, c in zip(rr[k], (st.h[k], st.hu[k], st.hv[k])):
                denom = max(float(jnp.max(jnp.abs(a))), 1.0)
                assert float(jnp.max(jnp.abs(a - c))) / denom < 1e-5, variant


# ---------------------------------------------------------------------------
# 4. ensemble path: batched dispatch draws bit-identical chains
# ---------------------------------------------------------------------------
def test_ensemble_chains_bit_identical_with_batching():
    import dataclasses

    from repro.configs.tohoku_mlda import CPU
    from repro.core import GaussianRandomWalk, balanced_mlda
    from repro.swe import (
        TohokuScenario,
        make_hierarchy,
        make_level_servers,
        train_level0_gp,
    )

    w = dataclasses.replace(
        CPU, coarse_grid=(16, 16), fine_grid=(24, 24), t_end_s=1200.0,
        gp_train_points=8, gp_opt_steps=8, n_chains=3, n_fine_samples=3,
        subchain_lengths=(3, 2), max_batch=4,
    )
    fine = TohokuScenario(nx=24, ny=24, t_end=w.t_end_s)
    coarse = TohokuScenario(nx=16, ny=16, t_end=w.t_end_s)
    h = make_hierarchy(fine=fine, coarse=coarse)
    prob, f_fine, f_coarse = (
        h["problem"], h["forward_fine"], h["forward_coarse"]
    )
    gp = train_level0_gp(
        f_coarse, prob, n_train=w.gp_train_points, steps=w.gp_opt_steps
    )

    def run(batch: bool):
        ww = dataclasses.replace(w, batch_solves=batch)
        servers = make_level_servers(
            ww, gp, f_coarse, f_fine,
            batch_forwards=(
                None, h["forward_coarse_batch"], h["forward_fine_batch"]
            ) if batch else None,
        )
        runner, lb = balanced_mlda(
            servers, prob.log_likelihood, prob.log_prior,
            GaussianRandomWalk(w.rw_step_km), list(w.subchain_lengths),
            batchable_levels=ww.batchable_levels, n_chains=w.n_chains,
            ensemble_seed=0, speculative=True, as_runner=True,
            **ww.batch_kwargs(),
        )
        res = runner.run(
            lambda c, rng: prob.sample_prior(rng)[0] * 0.5, w.n_fine_samples
        )
        hist = lb.telemetry.batch_histogram()
        table = res.samplers[0].stats_table()
        lb.shutdown()
        return res.chains, hist, table

    chains_b, hist_b, table_b = run(True)
    chains_p, hist_p, _ = run(False)
    assert np.array_equal(chains_b, chains_p)
    assert hist_p == {}  # per-request run never coalesces
    assert hist_b, "batched run recorded no dispatches"
    assert set(hist_b) <= {"level0", "level1", "level2"}
    # stats_table surfaces the per-level histogram next to Table-1 columns
    assert all("batch_hist" in row for row in table_b)
