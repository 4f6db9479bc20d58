"""On-chip benchmark of the load balancer's two workloads.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``: the configuration file under
``bench/configs/``, the traffic file under ``bench/traffic/`` and the
per-layer metric readers under ``bench/metrics/`` are all found by name.
"""
