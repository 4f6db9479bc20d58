"""Export the Tōhoku level pools over a socket (DESIGN.md §11).

The server half of the two-process deployment the paper runs (simulation
servers behind UM-Bridge, balancer in the sampling process): build the
workload's hierarchy + GP surrogate with the same
:func:`repro.swe.inversion.build_inversion` call as
``examples/tsunami_inversion.py``, wrap the resulting pool in a
:class:`~repro.net.server.ServerShell`, and serve until interrupted.
Both protocols share the port — this process is a valid UM-Bridge model
server (``GET /Info`` / ``POST /Evaluate``) *and* the binary-framing
endpoint our :class:`~repro.net.client.BinaryTransport` dials.

Two-process walkthrough (see examples/README.md):

    # terminal 1 — the simulation server
    PYTHONPATH=src python -m repro.launch.export --workload cpu --port 4242

    # terminal 2 — the balancer + sampler
    PYTHONPATH=src python examples/tsunami_inversion.py \
        --workload cpu --remote 127.0.0.1:4242

Ctrl-C drains gracefully: the listener closes first, in-flight
evaluations finish and ship, then the worker pool and every connection
thread join.
"""
from __future__ import annotations

import argparse
import threading
import time


def build_shell(w, *, host: str, port: int, levels: str = "all"):
    """Hierarchy + GP + level servers + shell, ready to ``start()``.

    ``levels`` restricts what this process exports ("all", or a
    comma-separated subset like "1,2" to keep the GP local to the
    sampling process and farm out only the PDE solves).
    """
    # Imports deferred: --help must not pay jax startup.
    from repro.net import ServerShell
    from repro.swe.inversion import build_inversion

    inv = build_inversion(w)
    prob = inv.problem
    servers = inv.level_servers()
    if levels != "all":
        keep = {f"level{int(x)}" for x in levels.split(",")}
        servers = [
            s for s in servers if keep & set(s.capacity_tags or keep)
        ]
    dim = 2  # Tōhoku source location (x, y) in km
    n_obs = int(len(prob.y_obs))
    tags = sorted({t for s in servers for t in (s.capacity_tags or ())})
    return ServerShell(
        servers,
        host=host,
        port=port,
        name=f"tohoku-{w.name}",
        input_sizes={t: [dim] for t in tags},
        output_sizes={t: [n_obs] for t in tags},
    )


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="Serve the Tōhoku level pools over TCP "
        "(binary framing + UM-Bridge HTTP on one port)."
    )
    ap.add_argument("--workload", default="cpu")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=4242)
    ap.add_argument(
        "--levels", default="all",
        help='exported levels: "all" or a subset like "1,2"',
    )
    args = ap.parse_args(argv)

    from repro.configs.tohoku_mlda import CONFIGS
    from repro.launch.compile_cache import enable_compile_cache

    w = CONFIGS[args.workload]
    enable_compile_cache()
    print(f"[export] building {w.name} hierarchy + GP "
          f"(coarse {w.coarse_grid}, fine {w.fine_grid}) ...")
    t0 = time.time()
    shell = build_shell(w, host=args.host, port=args.port, levels=args.levels)
    shell.start()
    host, port = shell.address
    print(f"[export] ready in {time.time() - t0:.1f}s — serving "
          f"{shell.tags} on {host}:{port} (Ctrl-C to drain and exit)")
    try:
        # Serve until interrupted; the accept loop runs on its own thread.
        threading.Event().wait()
    except KeyboardInterrupt:
        print("\n[export] draining in-flight evaluations ...")
    finally:
        shell.stop(drain=True)
        print("[export] stopped.")


if __name__ == "__main__":
    main()
