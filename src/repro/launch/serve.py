"""Serving driver: continuous-batching LM serving through the load balancer.

``python -m repro.launch.serve --arch qwen2-0.5b --requests 32``

Models run at their published widths on an accelerator and as
``.reduced()`` variants on the CPU; ``--reduced`` / ``--no-reduced``
overrides that default.

The dispatcher is the paper's contribution re-used at the LM layer
(DESIGN.md §10): prefill and decode are disaggregated into two balancer
tag families (``prefill:<variant>`` / ``decode:<variant>``) routed
``cost_aware`` across replicas, and each decode server is a
:class:`~repro.balancer.types.DecodePool` that admits requests into the
in-flight batch at token boundaries — generation lengths spanning two
orders of magnitude stream through without short requests queueing behind
long ones, the LM analogue of the paper's MLDA level heterogeneity.
``--mode generation`` runs the old request-per-generation baseline for
comparison; ``--kv paged`` swaps the slab pools for the block-table KV
pool (chunked prefill through the pool, block-granular admission);
``--mode speculative`` decodes through the layer-sliced self-draft.
Every mode emits bit-identical greedy tokens.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCHS
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime.serve_loop import ServingEngine, serving_metrics


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--arch",
        action="append",
        default=None,
        help="model variant(s); repeat for a heterogeneous pool",
    )
    ap.add_argument(
        "--reduced",
        action=argparse.BooleanOptionalAction,
        default=None,
        help="serve .reduced() variants (default: only on the CPU)",
    )
    ap.add_argument(
        "--mode",
        choices=["continuous", "generation", "paged", "speculative"],
        default="continuous",
    )
    ap.add_argument(
        "--kv",
        choices=["slab", "paged"],
        default="slab",
        help="decode-pool KV layout; --kv paged upgrades --mode continuous "
        "to the block-table pool",
    )
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=96)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument(
        "--blocks",
        type=int,
        default=None,
        help="usable KV blocks in the paged pool (default: fully provision "
        "--slots worst-case sequences)",
    )
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    names = args.arch or ["qwen2-0.5b"]
    reduced = args.reduced
    if reduced is None:
        reduced = jax.default_backend() == "cpu"
    variants = {n: (ARCHS[n].reduced() if reduced else ARCHS[n]) for n in names}

    if args.mode == "continuous" and args.kv == "paged":
        args.mode = "paged"  # same normalization the engine applies
    rng = np.random.default_rng(args.seed)
    engine = ServingEngine(
        variants,
        mode=args.mode,
        kv=args.kv,
        n_replicas=args.replicas,
        n_slots=args.slots,
        cache_len=args.cache_len,
        block_size=args.block_size,
        n_blocks=args.blocks,
        prefill_chunk=args.prefill_chunk,
        spec_k=args.spec_k,
    )
    with engine:
        # Warm the executables so the measured window is steady-state serving.
        for vname, cfg in variants.items():
            warm = rng.integers(0, cfg.vocab, size=(1, args.prompt_len))
            engine.submit(vname, warm, 2).result(timeout=600)

        # Open-loop load: every client submits up front (arrivals do not
        # wait on completions), generation lengths span ~2 orders of
        # magnitude like the paper's level runtimes.
        t0 = time.monotonic()
        gens = []
        for _ in range(args.requests):
            vname = names[int(rng.integers(len(names)))]
            n_new = int(rng.choice([1, 4, 16, 64], p=[0.4, 0.3, 0.2, 0.1]))
            prompt = rng.integers(0, variants[vname].vocab, size=(1, args.prompt_len))
            gens.append(engine.submit(vname, prompt, n_new))
        for g in gens:
            g.result(timeout=600)
        wall = time.monotonic() - t0

        m = serving_metrics(gens, wall, engine.summary())
        print(
            f"[serve:{args.mode}] {m['n_requests']} requests, {m['n_tokens']} tokens "
            f"in {wall:.3f}s -> {m['tokens_per_s']:.1f} tok/s"
        )
        print(
            f"[serve:{args.mode}] ttft mean={m['ttft_mean_s'] * 1e3:.2f}ms "
            f"p99={m['ttft_p99_s'] * 1e3:.2f}ms; per-token "
            f"p50={m['per_token_p50_s'] * 1e3:.2f}ms p99={m['per_token_p99_s'] * 1e3:.2f}ms"
        )
        for name, occ in m.get("slot_occupancy", {}).items():
            print(f"[serve:{args.mode}]   {name}: mean slot occupancy {occ:.2f}")
        for name, occ in m.get("block_occupancy", {}).items():
            print(f"[serve:{args.mode}]   {name}: mean block occupancy {occ:.2f}")
        for tag, sp in m.get("spec_accept", {}).items():
            print(
                f"[serve:{args.mode}]   {tag}: spec accept rate {sp['rate']:.2f} "
                f"({sp['accepted']}/{sp['drafted']} over {sp['rounds']} rounds)"
            )
        for row in engine.stats_table():
            print(
                f"[serve:{args.mode}]   {row['tag']}: {row['n_done']} done, "
                f"{row['tokens']} pooled tokens, ewma {row['ewma_s'] * 1e3:.2f}ms"
            )


if __name__ == "__main__":
    main()
