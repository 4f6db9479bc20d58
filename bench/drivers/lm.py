"""Driver of the language-model serving cells: open-loop traffic through
the balancer into a paged decode pool.

Set-up makes the weights on the device in one jitted call from the seed,
builds the pool as ``ServingEngine(mode="paged")`` does (the program's
paged decode pool per variant behind a :class:`~repro.balancer.LoadBalancer`),
and warms the decode step and the prefill chunk at every length from 1 to
``prefill_chunk`` with real requests.  The window sends the traffic's
schedule, each request through :class:`~repro.runtime.serve_loop.Generation`
(what ``ServingEngine.submit`` returns), on its due time whether or not
earlier ones finished; then it waits a bounded drain for the stragglers.
Every request is timed from its due time.  The end-to-end metrics are the
median and 80th percentile of the time to first token and the 99th
percentile of the gaps between tokens (:data:`TAILS`).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from bench import harness
from bench import traffic as gen
from bench import work
from bench.harness import Check, Outcome, now, quantile, span

MODEL_KEYS = (
    "hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "vocab_size", "rope_theta", "rms_norm_eps",
)


def model_sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return {k: cfg[k] for k in MODEL_KEYS}


def arch_config(cfg: Dict[str, Any]):
    """The program's :class:`ArchConfig` as the configuration file states it."""
    from repro.configs.base import ArchConfig

    if cfg["hidden_act"] != "silu" or not cfg["attention_qkv_bias"]:
        raise harness.BenchError("this driver serves SwiGLU decoders with q/k/v bias")
    return ArchConfig(
        arch_id=cfg["name"],
        family="dense",
        n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"],
        qkv_bias=True,
        mlp="swiglu",
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        param_dtype=cfg["torch_dtype"],
        compute_dtype=cfg["torch_dtype"],
        source=cfg["source"],
    )


def make_weights(cfg: Dict[str, Any], seed31: int):
    """Random weights in the served dtype, made on the device in one
    jitted call, in the program's parameter layout."""
    import jax
    import jax.numpy as jnp

    w = cfg["weights"]
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, ff, V = d // H, cfg["intermediate_size"], cfg["vocab_size"]
    dtype = jnp.dtype(cfg["torch_dtype"])

    @jax.jit
    def init(key):
        ks = iter(jax.random.split(key, 16))

        def normal(shape, std):
            return (jax.random.normal(next(ks), shape, jnp.float32) * std).astype(dtype)

        def norm_weight(shape):
            return (1.0 + w["norm_std"] * jax.random.normal(next(ks), shape)).astype(dtype)

        return {
            "blocks": {
                "ln1": norm_weight((L, d)),
                "attn": {
                    "wq": normal((L, d, H * hd), d**-0.5),
                    "wk": normal((L, d, kv * hd), d**-0.5),
                    "wv": normal((L, d, kv * hd), d**-0.5),
                    "wo": normal((L, H * hd, d), (H * hd) ** -0.5),
                    "bq": normal((L, H * hd), w["bias_std"]),
                    "bk": normal((L, kv * hd), w["bias_std"]),
                    "bv": normal((L, kv * hd), w["bias_std"]),
                },
                "ln2": norm_weight((L, d)),
                "mlp": {
                    "w_gate": normal((L, d, ff), d**-0.5),
                    "w_up": normal((L, d, ff), d**-0.5),
                    "w_down": normal((L, ff, d), ff**-0.5),
                },
            },
            "embed": normal((V, d), w["embed_std"]),
            "ln_f": norm_weight((d,)),
        }

    params = init(jax.random.key(seed31))
    jax.block_until_ready(params)
    return params


# The whole warm-up's budget: 4.9 x the 18.53 s cold warm-up of the 17
# programs of qwen2-0.5b's pool with the weights as jit arguments, on a
# TPU v5e.  A pool that closes the weights into each program compiles each
# for about 43 s and ran a 40 GiB host out of memory at the sixth.
WARM_BUDGET_S = 90.0


def paged_pool(cfg: Dict[str, Any], arch, params, name: str, tag: str):
    """The program's paged decode pool,
    :func:`repro.runtime.serve_loop.make_paged_decode_pool`, over the
    weights this run made, as ``ServingEngine(mode="paged")`` builds it
    for one replica.  The factory closes the weights into each compiled
    program (one per prefill chunk length, plus the decode step)."""
    from repro.models import build_model
    from repro.runtime.serve_loop import make_paged_decode_pool

    sv = cfg["serving"]
    return make_paged_decode_pool(
        build_model(arch), params,
        n_slots=int(sv["n_slots"]), cache_len=int(sv["cache_len"]),
        block_size=int(sv["block_size"]), prefill_chunk=int(sv["prefill_chunk"]),
        name=name, tag=tag,
    )


class LMCell:
    def __init__(self, ctx: harness.Context) -> None:
        from repro.balancer import LoadBalancer

        self.ctx = ctx
        cfg, self.tr = ctx.cell.config, ctx.cell.traffic
        self.cfg = cfg
        sv = cfg["serving"]
        self.variant = cfg["name"]
        self.cache_len = int(sv["cache_len"])
        self.chunk = int(sv["prefill_chunk"])
        self.params = make_weights(cfg, gen.int31(ctx.seed))
        pool = paged_pool(
            cfg, arch_config(cfg), self.params,
            name=f"paged:{self.variant}#0", tag=f"prefill:{self.variant}",
        )
        step, chunk = ctx.tampered("decode", pool.step_fn), pool.chunk_fn

        def step_fn(state, tokens, active):
            with span("bench.decode_step"):
                return step(state, tokens, active)

        def chunk_fn(state, slot, toks, start):
            with span("bench.prefill_chunk"):
                return chunk(state, slot, toks, start)

        pool.step_fn, pool.chunk_fn = step_fn, chunk_fn
        self.pool = pool
        self.lb = LoadBalancer([pool], policy=sv["policy"])

    def submit(self, prompt: np.ndarray, n_new: int):
        from repro.runtime.serve_loop import Generation

        return Generation(self.lb, self.variant, (prompt.astype(np.int64), int(n_new), None), "paged")

    def warm(self) -> str:
        """Prefill chunks of every length 1..prefill_chunk and the decode
        step, through the served path, within :data:`WARM_BUDGET_S`;
        returns a note on what it took.

        A pool whose programs compile far slower than that (one that takes
        the weights into each program as constants compiles for tens of
        seconds and some GB of host memory apiece) ends the run here with a
        :class:`~bench.harness.BenchError`, before the host runs out of
        memory."""
        budget = WARM_BUDGET_S
        rng = np.random.default_rng(0)
        vocab = self.cfg["vocab_size"]
        t0, (n0, s0) = now(), self.ctx.compile_clock.snapshot()

        def compiled():
            n, s = self.ctx.compile_clock.snapshot()
            return n - n0, s - s0

        gens = [
            self.submit(rng.integers(0, vocab, size=k), 2) for k in range(1, self.chunk + 1)
        ]
        for done, g in enumerate(gens):
            try:
                g.result(timeout=max(0.0, t0 + budget - now()))
            except TimeoutError:
                n, s = compiled()
                each = f" ({s / n:.1f} s each)" if n else ""
                raise harness.BenchError(
                    f"the pool's warm-up did not finish within WARM_BUDGET_S = {budget:g} s: "
                    f"{done} of {len(gens)} warm-up requests done, {n} programs compiled in "
                    f"{s:.1f} s{each}. Programs that take the weights as compile-time "
                    "constants compile this slowly; pass the weights as arguments"
                ) from None
        n, s = compiled()
        return f"[warm] {now() - t0:.3f} s (budget {budget:g} s), {n} compiles ({s:.3f} s)"

    def window(self, sched: gen.Schedule) -> Dict[str, Any]:
        ctx = self.ctx
        n = len(sched)
        gens: List = [None] * n
        late = np.zeros(n)
        before = self.lb.summary()
        c0 = ctx.compile_clock.snapshot()
        drain_s = float(self.tr["drain_s"])
        with ctx.traced():
            with span("bench.served"):
                with span("bench.window"):
                    t0 = now()
                    for i in range(n):
                        due = t0 + sched.due_s[i]
                        wait = due - now()
                        if wait > 0:
                            with span("bench.generator.wait"):
                                time.sleep(wait)
                        late[i] = now() - due
                        with span("bench.submit"):
                            gens[i] = self.submit(sched.prompts[i], sched.new_tokens[i])
                    rest = t0 + ctx.seconds - now()
                    if rest > 0:
                        time.sleep(rest)
                    t_close = now()
                with span("bench.drain"):
                    deadline = t_close + drain_s
                    for g in gens:
                        outcome(g, max(0.0, deadline - now()))
                t_end = now()
        c1 = ctx.compile_clock.snapshot()
        after = self.lb.summary()
        return dict(t0=t0, t_close=t_close, t_end=t_end, gens=gens, late=late,
                    before=before, after=after, compiles=c1[0] - c0[0],
                    compile_s=c1[1] - c0[1])

    def shutdown(self) -> None:
        self.lb.shutdown()
        self.pool = None


def outcome(g, timeout: float):
    """The request's result, or None if it failed or is not done in time."""
    try:
        return g.result(timeout=timeout)
    except TimeoutError:
        return None
    except Exception:  # noqa: BLE001 - a failed request counts as failed
        return None


def tally(sched: gen.Schedule, win: Dict[str, Any]):
    """TTFT per request from its due time (an unfinished request counts
    from its due time to the end of the drain), every inter-token gap, and
    the requests that failed: errored, unfinished, or short of tokens."""
    t0, t_end = win["t0"], win["t_end"]
    ttft, gaps, failed, done = [], [], [], []
    for i, g in enumerate(win["gens"]):
        due = t0 + sched.due_s[i]
        res = outcome(g, 0.0)
        if res is None or len(res.tokens) != int(sched.new_tokens[i]):
            failed.append(i)
            ttft.append(t_end - due)
            continue
        times = np.asarray(res.token_times, np.float64)
        ttft.append(times[0] - due)
        gaps.extend(np.diff(times).tolist())
        done.append((i, np.asarray(res.tokens)))
    return np.asarray(ttft), np.asarray(gaps), failed, done


# The serving cells' end-to-end metrics: (name, what it is taken over, q).
# TTFT over every request due in the window, the failed ones included; the
# inter-token gap over every gap of the requests that finished.
TAILS = (("ttft_ms_p50", "ttft", 0.5), ("ttft_ms_p80", "ttft", 0.8), ("itl_ms_p99", "itl", 0.99))
# Fewer samples than this beyond a percentile make it nearly a maximum.
MIN_BEYOND = 10


def tails(ttft: np.ndarray, gaps: np.ndarray) -> Dict[str, float]:
    """The serving metrics, in ms, from what :func:`tally` returns (an
    inter-token tail with no gap at all is infinite)."""
    xs = {"ttft": ttft, "itl": gaps}
    return {
        name: quantile(xs[of], q) * 1e3 if len(xs[of]) else float("inf")
        for name, of, q in TAILS
    }


def beyond(xs: np.ndarray, q: float) -> int:
    """How many samples lie above the ``q`` quantile."""
    return int(np.sum(xs > quantile(xs, q))) if len(xs) else 0


def tail_note(ttft: np.ndarray, gaps: np.ndarray) -> str:
    """The samples beyond each percentile, and a warning where the traffic's
    rate leaves fewer than :data:`MIN_BEYOND` beyond one."""
    xs = {"ttft": ttft, "itl": gaps}
    counts = {name: beyond(xs[of], q) for name, of, q in TAILS}
    few = [name for name, k in counts.items() if k < MIN_BEYOND]
    line = "[tails] samples beyond: " + ", ".join(f"{n} {k}" for n, k in counts.items())
    if few:
        line += f"; fewer than {MIN_BEYOND} beyond {', '.join(few)}: raise the rate or the window"
    return line


def pick_checked(done, sched: gen.Schedule, k: int, seed: int):
    """The longest finished request plus ``k - 1`` others drawn from the seed."""
    if not done:
        return []
    size = lambda item: sched.prompt_len[item[0]] + sched.new_tokens[item[0]]  # noqa: E731
    longest = max(range(len(done)), key=lambda j: size(done[j]))
    rest = [j for j in range(len(done)) if j != longest]
    rng = np.random.default_rng(gen.seed_words(seed).spawn(3)[2])
    extra = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) if rest else []
    return [done[longest]] + [done[rest[j]] for j in extra]


def widest_gap(params, cfg, sched, picked, cache_len: int, mode: str = "f32") -> float:
    from bench.reference import qwen2

    gaps = [
        qwen2.served_gaps(params, sched.prompts[i], toks, model_sizes(cfg), cache_len, mode)
        for i, toks in picked
    ]
    return float(max(np.max(g) for g in gaps)) if gaps else float("inf")


def readings(ctx: harness.Context, seeds):
    """For each seed, a short window at the cell's own load and the served
    logit gap of the program and of the control (the reference with fp8
    matmul inputs, at the same prompts and tokens); the limit of
    ``correct`` is set from these (``bench/limits.py``)."""
    import dataclasses
    import gc

    cfg = ctx.cell.config
    for seed in seeds:
        c = dataclasses.replace(ctx, seed=seed)
        cell = LMCell(c)
        cell.warm()
        sched = gen.open_loop(cell.tr, cfg["vocab_size"], seed, c.seconds)
        win = cell.window(sched)
        cell.shutdown()
        _, _, failed, done = tally(sched, win)
        picked = pick_checked(done, sched, int(cell.tr["check_requests"]), seed)
        got = widest_gap(cell.params, cfg, sched, picked, cell.cache_len)
        low = widest_gap(cell.params, cfg, sched, picked, cell.cache_len, mode="fp8")
        del cell
        gc.collect()
        yield {"seed": seed, "failed": len(failed), "served_tokens": sum(len(t) for _, t in picked),
               "program": {"served_logit_gap": got}, "control": {"served_logit_gap": low}}


def run(ctx: harness.Context) -> Outcome:
    import jax

    cfg = ctx.cell.config
    cell = LMCell(ctx)
    warm_note = cell.warm()
    sched = gen.open_loop(cell.tr, cfg["vocab_size"], ctx.seed, ctx.seconds)
    win = cell.window(sched)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    before, after = win["before"], win["after"]
    cell.shutdown()
    ttft, gaps, failed, done = tally(sched, win)
    finished = {i for i, _ in done}
    flops = sum(
        work.lm_request_flops(model_sizes(cfg), int(sched.prompt_len[i]), int(sched.new_tokens[i]))
        for i in finished
    )
    facts = {
        "requests": len(sched),
        "flops": flops,
        "served_span": "bench.served",
        "decode_program": "jit_step_j",
        "prefill_program": "jit_chunk_j",
        "prefill_tokens": int(sum(sched.prompt_len[i] for i in finished)),
        "decoded_tokens": int(sum(sched.new_tokens[i] for i in finished)),
    }
    picked = pick_checked(done, sched, int(cell.tr["check_requests"]), ctx.seed)
    t_ref = now()
    gap = widest_gap(cell.params, cfg, sched, picked, cell.cache_len)
    late = win["late"]
    window_s = win["t_close"] - win["t0"]
    notes = [
        f"[setup] {win['t0'] - ctx.process_start:.3f} s to the window",
        warm_note,
        f"[generator] lateness over {len(late)} requests: p50 {np.median(late) * 1e3:.3f} ms, "
        f"p95 {quantile(late, 0.95) * 1e3:.3f} ms, max {late.max() * 1e3:.3f} ms",
        f"[window] {window_s:.3f} s, {len(sched)} requests due, {len(failed)} failed, "
        f"drain {win['t_end'] - win['t_close']:.3f} s, compiles in window: "
        f"{win['compiles']} ({win['compile_s']:.3f} s)",
        f"[reference] {now() - t_ref:.3f} s over {len(picked)} requests, "
        f"{sum(len(t) for _, t in picked)} served tokens",
    ]
    notes.append(tail_note(ttft, gaps))
    return Outcome(
        end_to_end={**tails(ttft, gaps), "setup_s": win["t0"] - ctx.process_start},
        attempted=len(sched),
        failed=len(failed),
        checks=[
            Check("failed_requests", float(len(failed)), 0.0),
            Check("served_logit_gap", gap, float(cfg["limits"]["served_logit_gap"])),
        ],
        memory_peak_bytes=peak,
        facts=facts,
        before=before,
        after=after,
        notes=notes,
    )
