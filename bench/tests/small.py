"""Small cells for the CPU tests: the committed configurations and
traffic, cut in size so a test run holds them."""
from __future__ import annotations

import copy
import json
import time
from pathlib import Path

from bench import harness

BENCH = Path(__file__).resolve().parents[1]


def load(kind: str, name: str) -> dict:
    with (BENCH / kind / f"{name}.json").open() as f:
        return json.load(f)


def mlda_config() -> dict:
    cfg = copy.deepcopy(load("configs", "tohoku-mlda-paper"))
    cfg.update(coarse_grid=[16, 16], fine_grid=[24, 24], max_batch=4)
    cfg["scenario"]["t_end_s"] = 1800.0
    cfg["gp"].update(train_points=32, adam_steps=20)
    return cfg


def mlda_traffic(resident: bool) -> dict:
    tr = copy.deepcopy(load("traffic", "mlda-resident-64chains" if resident else "mlda-5chains"))
    tr.update(chains=4 if resident else 2, warm_samples=2)
    return tr


def lm_config() -> dict:
    cfg = copy.deepcopy(load("configs", "qwen2-0.5b"))
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
               torch_dtype="float32")
    cfg["serving"].update(n_slots=4, cache_len=64, prefill_chunk=4)
    return cfg


def lm_traffic() -> dict:
    tr = copy.deepcopy(load("traffic", "chat-steady"))
    tr.update(rate_per_s=8.0, drain_s=60, check_requests=2)
    tr["prompt"].update(median=12, min=2, max=24)
    tr["output"].update(median=6, min=2, max=12)
    return tr


def serving_metrics(cell_name: str) -> tuple:
    """The serving cell's end-to-end and per-layer entries, as the PR that
    brings the first serving cell adds them to BENCHMARK.json (bounds from
    the chip, PERF.md section 2).  BENCHMARK.json lists no metric without a
    cell that reports it, so they are not in the committed file yet."""
    def e2e(name, bound):
        return {"name": name, "unit": "ms", "better": "lower", "bound": bound,
                "source": "host_clock", "workloads": [cell_name]}

    def layer(name, unit, better, source, layer_name, moves):
        return {"name": name, "unit": unit, "better": better, "source": source,
                "layer": layer_name, "moves": moves, "workloads": [cell_name]}

    return (
        [e2e("ttft_ms_p50", 0.25), e2e("ttft_ms_p80", 0.25), e2e("itl_ms_p99", 0.08)],
        [
            layer("decode_step_ms.serve", "ms", "lower", "device_trace", "programs", "itl_ms_p99"),
            layer("queue_wait_ms_mean.serve", "ms", "lower", "program_counter", "dispatcher",
                  "ttft_ms_p80"),
            layer("slot_occupancy.serve", "%", "higher", "program_counter", "dispatcher",
                  "itl_ms_p99"),
            layer("mfu.serve", "%", "higher", "device_trace", "whole step", "ttft_ms_p50"),
            layer("device_idle_share.serve", "%", "lower", "device_trace", "device",
                  "ttft_ms_p50"),
        ],
    )


def cell(config: dict, traffic: dict, name: str = "small") -> harness.Cell:
    return harness.Cell(name, 1, config, traffic, [], [], BENCH)


def context(c: harness.Cell, seed: int = 7, seconds: float = 1.0, tamper=None) -> harness.Context:
    return harness.Context(
        cell=c, seed=seed, seconds=seconds, trace=False,
        process_start=time.monotonic(), compile_clock=harness.CompileClock(),
        require_tpu=False, tamper=tamper,
    )
