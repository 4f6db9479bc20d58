"""Server-pool wiring for the Tōhoku MLDA workload (DESIGN.md §8).

Shared by ``examples/tsunami_inversion.py`` and
``benchmarks/bench_mlda.py`` so the example and the benchmark always
measure the same pool layout (``MLDAWorkloadConfig.servers_per_level``).

With ``MLDAWorkloadConfig.batch_solves`` (the default) every server is a
:class:`repro.balancer.types.BatchServer`: its handler takes a stacked
``(B, ...)`` parameter array, so the dispatcher's coalescing path runs a
whole same-level batch as ONE vmapped AOT executable launch instead of B
back-to-back solves.  Pass the scenario-built batch forwards via
``batch_forwards=(gp_batch, coarse_batch, fine_batch)`` or let this module
derive them (``gp.batch_call`` exists on the GP; SWE levels need the
``TohokuScenario.build_batch_forward`` callables).

With a :class:`repro.runtime.sharding.ShardingPolicy` (``policy=``) a level
whose *traceable* stacked forward is available (``stacked_forwards=``, from
``TohokuScenario.build_stacked_forward``) becomes ONE
:class:`repro.balancer.types.ShardedBatchServer` pool instead of
``servers_per_level`` thread replicas: the coalesced batch is
``shard_map``'d over the data axes of the device mesh, so the balancer
schedules across mesh shards, not threads (DESIGN.md §9).

Each level's tag (``level0``/``level1``/``level2``) is a key in the
dispatcher's per-tag queue and free-server indexes (DESIGN.md §2): the
coalescing window fires early the moment ``max_batch`` same-level solves
are queued, so a saturated level never idles a pool slot waiting out
``batch_window_s``, and a lone solve never pays the window at all.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.balancer import BatchServer, Server, ShardedBatchServer


def make_level_servers(
    w,
    gp: Callable,
    f_coarse: Callable,
    f_fine: Callable,
    *,
    batch_forwards: Optional[Sequence[Optional[Callable]]] = None,
    stacked_forwards: Optional[Sequence[Optional[Callable]]] = None,
    policy=None,
) -> List[Server]:
    """One GP server + the config's per-level coarse/fine SWE servers.

    ``np.asarray`` forces each (async-dispatched) jax solve to materialise
    ON the worker thread: the server's busy interval covers the real
    compute and the GIL is released while XLA runs, so solves from
    different chains genuinely overlap.

    When ``w.batch_solves`` is set, a level whose batched forward is
    available becomes a :class:`BatchServer` (stacked ``(B, ...)`` in, one
    result row per member out) capped at ``w.max_batch``; levels without
    one fall back to per-request servers.  ``batch_forwards`` is
    ``(level0, level1, level2)`` stacked handlers — ``None`` entries fall
    back too.  The GP's own :meth:`~repro.core.gp.GaussianProcess.batch_call`
    (one compiled program per batch size) is used automatically when no
    explicit level-0 handler is given.

    When ``policy`` (a :class:`~repro.runtime.sharding.ShardingPolicy`) is
    also given, levels with a traceable stacked forward
    (``stacked_forwards``; the GP's ``batch_call`` again fills level 0)
    become a single :class:`ShardedBatchServer` pool each —
    ``servers_per_level`` replica counts are ignored for those levels,
    since the mesh shards replace the thread replicas.
    """
    batching = bool(getattr(w, "batch_solves", False))
    max_batch = int(getattr(w, "max_batch", 8)) or None
    if policy is None and batching and getattr(w, "mesh_devices", None):
        # The config asked for a device mesh (MLDAWorkloadConfig.mesh_devices)
        # without the caller building a policy: derive it here so setting the
        # knob alone shards the pools.
        from repro.runtime.sharding import data_mesh, data_policy

        policy = data_policy(data_mesh(w.mesh_devices))
    bf = list(batch_forwards or (None, None, None))
    while len(bf) < 3:
        bf.append(None)
    if batching and bf[0] is None and hasattr(gp, "batch_call"):
        bf[0] = gp.batch_call
    sf = list(stacked_forwards or (None, None, None))
    while len(sf) < 3:
        sf.append(None)
    if policy is not None and sf[0] is None and hasattr(gp, "batch_call"):
        sf[0] = gp.batch_call

    def sharded(level: int) -> bool:
        return batching and policy is not None and sf[level] is not None

    def batched(fn: Callable) -> Callable:
        return lambda ts: np.asarray(fn(jnp.asarray(ts)))

    def server(level: int, single: Callable, name: str, tag: str) -> Server:
        if sharded(level):
            return ShardedBatchServer(
                sf[level], policy, name=name, capacity_tags=(tag,),
                max_batch=max_batch, cache_key=("pool", tag),
            )
        if batching and bf[level] is not None:
            return BatchServer(
                batched(bf[level]), name=name, capacity_tags=(tag,),
                max_batch=max_batch,
            )
        return Server(
            lambda t: np.asarray(single(jnp.asarray(t))),
            name=name, capacity_tags=(tag,),
        )

    servers = [server(0, gp, "gp-0", "level0")]
    if sharded(1):
        servers.append(server(1, f_coarse, "coarse-pool", "level1"))
    else:
        for i in range(max(w.servers_per_level.get(1, 1), 1)):
            servers.append(server(1, f_coarse, f"coarse-{i}", "level1"))
    if sharded(2):
        servers.append(server(2, f_fine, "fine-pool", "level2"))
    else:
        for i in range(max(w.servers_per_level.get(2, 1), 1)):
            servers.append(server(2, f_fine, f"fine-{i}", "level2"))
    return servers


def make_remote_level_servers(
    w,
    addresses: Sequence[str],
    *,
    binary: Optional[bool] = None,
) -> List[Server]:
    """Remote replicas of the level pools: the client half of a
    two-process deployment (DESIGN.md §11).

    Each address is a ``host:port`` endpoint running
    ``python -m repro.launch.export`` (a
    :class:`~repro.net.server.ServerShell` over the pool
    :func:`make_level_servers` builds there).  One shared transport per
    endpoint — its pipelined connection pool multiplexes every level tag —
    and one :class:`~repro.net.client.RemoteBatchServer` per exported tag,
    so the dispatcher's coalescing path ships a stacked ``(B, ...)`` batch
    as ONE framed call.  Replicated tags across endpoints behave exactly
    like replicated local servers: the policy balances across them, and a
    dead endpoint's in-flight members requeue onto the survivors.

    ``binary=None`` takes ``w.remote_binary``; transports must be closed
    by the caller (``server.transport.close()`` once per distinct
    transport) after the balancer shuts down.
    """
    from repro.net import make_transport, remote_servers_for

    kwargs = dict(w.remote_kwargs()) if hasattr(w, "remote_kwargs") else {}
    if binary is not None:
        kwargs["binary"] = binary
    timeout = kwargs.get("read_timeout")
    servers: List[Server] = []
    for addr in addresses:
        transport = make_transport(addr, **kwargs)
        servers.extend(
            remote_servers_for(
                transport,
                batch=bool(getattr(w, "batch_solves", True)),
                max_batch=int(getattr(w, "max_batch", 8)) or None,
                name_prefix=f"remote-{addr}",
                request_timeout=timeout,
            )
        )
    return servers
