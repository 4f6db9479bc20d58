"""A request's life inside the program, as counters and profiler spans.

1. The dispatcher stamps each request's queue delay in three parts —
   decision, worker pickup, dispatch — that add up to it exactly, and
   ``summary()['wait_split']`` sums them per tag and over all tags.
2. The chain runner books the time from a request's completion to its
   resumption (``summary()['resume']``), and the dispatcher and runner put
   ``repro.*`` spans into a profiler trace, tied together by the
   request's ``seq``.
3. The batched shallow-water forwards compile under a name that carries
   their grid.
"""
from __future__ import annotations

import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro.balancer import BatchServer, LoadBalancer, Request, Server
from repro.balancer.telemetry import Telemetry


# ---------------------------------------------------------------------------
# 1. the wait split
# ---------------------------------------------------------------------------
def _drive(lb: LoadBalancer, n_threads: int = 4, per_thread: int = 12):
    """Client threads submitting batchable fine requests and plain GP ones."""
    done = []
    lock = threading.Lock()

    def client(k):
        for i in range(per_thread):
            tag = "fine" if (i + k) % 3 else "gp"
            req = lb.submit_async(np.full(2, float(i)), tag=tag, batchable=tag == "fine")
            lb.result(req, timeout=10)
            with lock:
                done.append(req)

    threads = [threading.Thread(target=client, args=(k,)) for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    return done


@pytest.mark.parametrize(
    "exact, n_threads, switch_s",
    [(False, 4, None), (True, 4, None), (False, 32, 1e-6)],
    ids=["streaming", "exact", "stress"],
)
def test_wait_split_adds_up_to_queue_delay(exact, n_threads, switch_s):
    """Per request the three parts add up to the queue delay; summed, they
    add up to the idle moments.  The stress case runs more client threads
    than cores with a short switch interval, where a lost update of a
    shared sum would show."""

    def slow(stacked):
        time.sleep(0.004)
        return stacked * 2.0

    lb = LoadBalancer(
        [
            BatchServer(slow, max_batch=4, name="slow", capacity_tags=("fine",)),
            Server(lambda t: t + 1.0, name="gp", capacity_tags=("gp",)),
        ],
        batch_window_s=0.01,
        batch_window_frac=100.0,  # the window is the cap: it gets armed
        exact_telemetry=exact,
    )
    old_switch = sys.getswitchinterval()
    try:
        if switch_s is not None:
            sys.setswitchinterval(switch_s)
        done = _drive(lb, n_threads=n_threads)
    finally:
        sys.setswitchinterval(old_switch)
    s = lb.summary()
    lb.shutdown()
    assert len(done) == 12 * n_threads and s["n_requests"] == len(done)
    for r in done:
        wait, handoff, coalesce = r.wait_split()
        assert wait + handoff + coalesce == pytest.approx(r.queue_delay, abs=1e-9)
        assert min(wait, handoff, coalesce) >= 0.0
    assert any(r.wait_split()[2] > 0 for r in done), "no coalescing window was held"
    assert max(s["batch_histogram"]["fine"]) > 1, "nothing coalesced"
    split = s["wait_split"]
    assert set(split) == {"*", "fine", "gp"}
    assert split["*"]["n"] == s["n_requests"] == split["fine"]["n"] + split["gp"]["n"]
    whole = split["*"]["dispatch_wait_s"] + split["*"]["handoff_s"] + split["*"]["coalesce_s"]
    assert whole == pytest.approx(s["mean_idle_s"] * s["n_requests"], abs=1e-9)
    for i, part in enumerate(("dispatch_wait_s", "handoff_s", "coalesce_s")):
        assert split["*"][part] == pytest.approx(sum(r.wait_split()[i] for r in done), abs=1e-9)
    for part in ("dispatch_wait_s", "handoff_s", "coalesce_s"):
        assert split["*"][part] == pytest.approx(split["fine"][part] + split["gp"][part])
    assert split["gp"]["coalesce_s"] == 0.0  # plain server: never a window


def test_hedge_repair_moves_the_wait_split_too():
    t = Telemetry()
    server = Server(lambda x: x, name="s0")

    def completed(decided, picked, dispatched):
        r = Request(theta=0, tag="t", arrived_at=100.0, decided_at=decided,
                    picked_at=picked, dispatched_at=dispatched, completed_at=dispatched + 0.01)
        r.done.set()
        t.record_completion(r, server)
        return r

    winner = completed(100.001, 100.002, 100.003)
    loser = completed(100.1, 100.2, 100.5)
    loser.hedged = True
    t.rebook_hedged(winner, loser)
    split = t.summary([server])["wait_split"]["*"]
    assert split["n"] == 1
    assert split["dispatch_wait_s"] == pytest.approx(0.001)
    assert split["handoff_s"] == pytest.approx(0.001)
    assert split["coalesce_s"] == pytest.approx(0.001)
    # a request that never passed a decision books its delay as the first part
    bare = Request(theta=0, tag="t", arrived_at=5.0, dispatched_at=5.25)
    assert bare.wait_split() == (0.25, 0.0, 0.0)


# ---------------------------------------------------------------------------
# 2. the chain runner: resumption counter and spans
# ---------------------------------------------------------------------------
def _profile_events(path: Path):
    """``(name, start_ns, thread, stats)`` of every ``repro.*`` host event."""
    from jax.profiler import ProfileData

    files = sorted(path.rglob("*.xplane.pb"))
    assert files, "the profiler wrote no .xplane.pb"
    out = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        for pos, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    thread = (plane.name, pos)
                    out.append((e.name, e.start_ns, thread, dict(e.stats)))
    return out


def test_runner_spans_and_resume_counter(tmp_path):
    import jax

    from repro.core import GaussianRandomWalk, balanced_mlda

    def fine(t):
        time.sleep(0.002)  # long enough for the runner to sleep on it
        return t

    servers = [
        Server(lambda t: t, name="gp-0", capacity_tags=("level0",)),
        Server(fine, name="fine-0", capacity_tags=("level1",)),
    ]
    runner, lb = balanced_mlda(
        servers,
        lambda obs: float(-0.5 * np.sum(np.asarray(obs) ** 2)),
        lambda t: 0.0,
        GaussianRandomWalk(1.0),
        [2],
        n_chains=2,
        ensemble_seed=3,
    )
    with jax.profiler.trace(str(tmp_path)):
        res = runner.run(lambda c, rng: rng.normal(size=2), 6)
    s = lb.summary()
    lb.shutdown()
    assert res.chains.shape == (2, 6, 2)
    assert s["resume"]["n"] == s["n_requests"] > 0
    assert s["resume"]["sum_s"] >= 0.0

    events = _profile_events(tmp_path)
    names = {name for name, *_ in events}
    assert {"repro.dispatch.serve", "repro.runner.step", "repro.runner.wait"} <= names
    serves = [(t0, st) for name, t0, _, st in events if name == "repro.dispatch.serve"]
    steps = [(t0, st) for name, t0, _, st in events if name == "repro.runner.step"]
    assert all({"tag", "req", "rows"} <= set(st) for _, st in serves)
    assert all({"chain", "req"} <= set(st) for _, st in steps)
    # one request's spans share its seq: the serve, then the step that resumed on it
    joined = [
        (t_serve, st["req"])
        for t_serve, st in serves
        if any(sst["req"] == st["req"] and t_step > t_serve for t_step, sst in steps)
    ]
    assert joined, "no step span resumed on a served request"
    # the serve spans run on the dispatcher's workers, not on the runner's thread
    threads = {name: set() for name in names}
    for name, _, thread, _ in events:
        threads[name].add(thread)
    assert not threads["repro.dispatch.serve"] & threads["repro.runner.wait"]


def test_runner_without_balancer_books_nothing():
    from repro.core import GaussianRandomWalk, MLDASampler
    from repro.ensemble import EnsembleRunner

    runner = EnsembleRunner(
        lambda c: MLDASampler(
            [lambda t: -float(np.sum(t**2)), lambda t: -0.5 * float(np.sum(t**2))],
            GaussianRandomWalk(1.0), [2],
        ),
        2,
        seed=1,
    )
    assert runner.balancer is None
    assert runner.run(np.zeros(2), 5).chains.shape == (2, 5, 2)


# ---------------------------------------------------------------------------
# 3. program names
# ---------------------------------------------------------------------------
def test_batched_forward_programs_carry_the_grid():
    import jax.numpy as jnp

    from repro.swe import TohokuScenario

    sc = TohokuScenario(nx=24, ny=16, t_end=300.0)
    batched = sc.build_batch_forward()
    out = batched(jnp.asarray([[0.0, 0.0], [10.0, -5.0], [-20.0, 5.0]]))
    assert out.shape == (3, 4)
    (exe,) = batched.executables.values()
    assert exe.as_text().startswith("HloModule jit_swe_forward_16x24")
