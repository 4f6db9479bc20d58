"""Vectorised, fully-compiled MLDA (beyond-paper; DESIGN.md §2).

The paper's architecture evaluates one forward solve per HTTP request.  On a
TPU the natural execution model is *lockstep*: advance many chains at once,
with every density evaluation batched.  This module builds the entire MLDA
recursion (randomised-length subchains included) as one pure JAX program:

  * chains are vmapped — the level-0 GP density evaluates for all chains in
    a single batched call (the balancer's micro-task batching, but fused at
    compile time);
  * randomised subchain lengths are drawn per chain per step and realised by
    masking a fixed 2n-1 iteration scan (lockstep-safe);
  * everything lives under ``lax.scan`` so the sampler itself is one XLA
    executable — per-request overhead is *zero*, the logical conclusion of
    the paper's 'eliminate per-request initialisation' insight.

Correctness: the masked-scan subchain is distributionally identical to the
Python recursion in :mod:`repro.core.mlda` (tests/test_mlda.py checks both
against closed-form posteriors).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .mh import Proposal


class MLDAResult(NamedTuple):
    chain: jax.Array  # (..., n_samples, d) fine-level states
    logp: jax.Array  # (..., n_samples)
    accepts: jax.Array  # (..., n_levels) accepted transitions per level
    proposals: jax.Array  # (..., n_levels) proposed transitions per level


def make_mlda_kernel(
    log_posteriors: Sequence[Callable],
    subchain_lengths: Sequence[int],
    step_scale,
    *,
    randomize: bool = True,
):
    """Build ``sample(key, theta0, n_samples) -> MLDAResult`` for one chain.

    ``log_posteriors`` are pure JAX callables coarse->fine; ``step_scale`` is
    the level-0 random-walk scale (scalar or per-dim).

    Every ``chain(level)`` closure returns ``(theta, logp, counts)`` with
    ``counts`` of shape ``(level + 1, 2)`` holding (accepted, proposed) for
    levels ``0..level`` — a uniform signature that makes the recursion over
    levels trivially composable under ``lax.scan``.
    """
    n_levels = len(log_posteriors)
    if len(subchain_lengths) != n_levels - 1:
        raise ValueError("need one subchain length per level above 0")
    step_scale = jnp.asarray(step_scale)

    def _t_max(level: int) -> int:
        n = subchain_lengths[level - 1]
        return (2 * n - 1) if randomize else n

    def _draw_length(key, level: int):
        n = subchain_lengths[level - 1]
        if not randomize or n <= 1:
            return jnp.asarray(n, jnp.int32)
        return jax.random.randint(key, (), 1, 2 * n)  # uniform {1..2n-1}

    def make_chain(level: int):
        """fn(key, theta, logp_level, length, t_fixed) -> (theta, logp, counts)

        Runs ``t_fixed`` lockstep iterations, of which only the first
        ``length`` update state (masked randomised subchain length).
        """
        if level == 0:

            def chain0(key, theta, logp, length, t_fixed):
                def body(carry, key):
                    theta, logp, i, acc, prop = carry
                    k1, k2 = jax.random.split(key)
                    cand = theta + jax.random.normal(k1, theta.shape) * step_scale
                    logp_cand = log_posteriors[0](cand)
                    active = i < length
                    accept = (
                        jnp.log(jax.random.uniform(k2)) < (logp_cand - logp)
                    ) & active
                    theta = jnp.where(accept, cand, theta)
                    logp = jnp.where(accept, logp_cand, logp)
                    return (
                        theta,
                        logp,
                        i + 1,
                        acc + accept.astype(jnp.int32),
                        prop + active.astype(jnp.int32),
                    ), None

                z = jnp.zeros((), jnp.int32)
                (theta, logp, _, acc, prop), _ = jax.lax.scan(
                    body, (theta, logp, z, z, z), jax.random.split(key, t_fixed)
                )
                return theta, logp, jnp.stack([acc, prop])[None, :]  # (1, 2)

            return chain0

        lower = make_chain(level - 1)
        t_low = _t_max(level)

        def chain(key, theta, logp, length, t_fixed):
            logp_low = log_posteriors[level - 1](theta)

            def one_step(carry, key):
                theta, logp, logp_low, i, acc, prop = carry
                kl, ka, ku = jax.random.split(key, 3)
                sub_len = _draw_length(kl, level)
                psi, logp_psi_low, counts_low = lower(
                    ka, theta, logp_low, sub_len, t_low
                )
                logp_psi = log_posteriors[level](psi)
                active = i < length
                # alpha = pi_l(psi) pi_{l-1}(theta) / (pi_l(theta) pi_{l-1}(psi))
                log_alpha = (logp_psi - logp) + (logp_low - logp_psi_low)
                accept = (jnp.log(jax.random.uniform(ku)) < log_alpha) & active
                theta = jnp.where(accept, psi, theta)
                logp = jnp.where(accept, logp_psi, logp)
                logp_low = jnp.where(accept, logp_psi_low, logp_low)
                return (
                    theta,
                    logp,
                    logp_low,
                    i + 1,
                    acc + accept.astype(jnp.int32),
                    prop + active.astype(jnp.int32),
                ), counts_low

            z = jnp.zeros((), jnp.int32)
            (theta, logp, _, _, acc, prop), counts_low = jax.lax.scan(
                one_step,
                (theta, logp, logp_low, z, z, z),
                jax.random.split(key, t_fixed),
            )
            counts_low = jnp.sum(counts_low, axis=0)  # (level, 2)
            counts = jnp.concatenate(
                [counts_low, jnp.stack([acc, prop])[None, :]], axis=0
            )
            return theta, logp, counts  # counts: (level + 1, 2)

        return chain

    top = n_levels - 1
    top_chain = make_chain(top)

    def sample(key, theta0, n_samples: int) -> MLDAResult:
        theta0 = jnp.asarray(theta0)
        logp0 = log_posteriors[top](theta0)
        one = jnp.asarray(1, jnp.int32)

        def body(carry, key):
            theta, logp = carry
            theta, logp, counts = top_chain(key, theta, logp, one, 1)
            return (theta, logp), (theta, logp, counts)

        (_, _), (chain_out, logps, counts) = jax.lax.scan(
            body, (theta0, logp0), jax.random.split(key, n_samples)
        )
        counts = jnp.sum(counts, axis=0)  # (n_levels, 2)
        return MLDAResult(
            chain=chain_out,
            logp=logps,
            accepts=counts[:, 0],
            proposals=counts[:, 1],
        )

    return sample


def run_chains(
    log_posteriors: Sequence[Callable],
    subchain_lengths: Sequence[int],
    step_scale,
    key: jax.Array,
    theta0: jax.Array,  # (n_chains, d)
    n_samples: int,
    *,
    randomize: bool = True,
) -> MLDAResult:
    """vmap the compiled MLDA kernel over chains (lockstep parallel chains)."""
    kern = make_mlda_kernel(
        log_posteriors, subchain_lengths, step_scale, randomize=randomize
    )
    keys = jax.random.split(key, theta0.shape[0])
    fn = jax.jit(jax.vmap(lambda k, t0: kern(k, t0, n_samples)))
    return fn(keys, theta0)


# ---------------------------------------------------------------------------
# Device-resident ensemble (DESIGN.md §9)
#
# The lockstep kernel above is distributionally correct but draws its RNG on
# masked iterations too, so it can never be compared bit-for-bit against the
# Python step machine.  The ensemble kernel below uses *counter-mode* RNG
# instead: every chain carries one key plus a draw counter, each draw is
# ``fold_in(key, counter)``, and the counter advances ONLY when the Python
# machine would have consumed a draw (conditional consumption under the
# lockstep masks).  Driving :class:`repro.core.mlda.MLDASampler` with the
# :class:`CounterStream` shim below replays the identical stream on the
# host, which makes the fused ``(C,)``-vmapped chains bit-identical (fp32)
# to ``C`` independent Python step machines — tests/test_device_ensemble.py.
# ---------------------------------------------------------------------------


class EnsembleState(NamedTuple):
    """Device-resident state of ``C`` MLDA chains, ``(C,)``-leading.

    ``logp`` is the density of ``theta`` at the *top* level (the remote
    level in coupled mode), ``logp_low`` one level below it (zeros for a
    single-level hierarchy).  ``keydata`` holds the raw per-chain threefry
    keys (``jax.random.key_data``) so the whole state is a plain-array
    pytree that AOT caches and ``shard_map`` can handle.  ``counts`` is
    ``(C, n_levels, 3)`` int32 ``(n_accepted, n_proposed, n_evals)`` —
    exactly the :class:`repro.core.mlda.LevelRecord` totals.
    """

    theta: jax.Array  # (C, d) float32
    logp: jax.Array  # (C,) pi_top(theta)
    logp_low: jax.Array  # (C,) pi_{top-1}(theta)
    keydata: jax.Array  # (C, 2) uint32 raw chain keys
    counter: jax.Array  # (C,) int32 RNG draw counter
    counts: jax.Array  # (C, n_levels, 3) int32 (accepted, proposed, evals)


class PendingProposal(NamedTuple):
    """Coupled-mode hand-off: one top-level proposal per chain.

    ``u`` is the accept uniform, already (conditionally) consumed by
    :meth:`DeviceEnsemble.propose` so the device stream position matches
    the Python machine's; chains with ``moved == False`` took the MLDA
    unmoved shortcut (proposal == current state: auto-accepted upstream,
    no fine solve, no uniform consumed — ``u`` is garbage there).
    """

    psi: jax.Array  # (C, d) proposed fine states
    logp_psi_low: jax.Array  # (C,) pi_{top-1}(psi)
    u: jax.Array  # (C,) accept uniforms (valid where moved)
    moved: jax.Array  # (C,) bool — chain needs a fine-level solve


def _key_of(keydata: jax.Array) -> jax.Array:
    return jax.random.wrap_key_data(keydata, impl="threefry2x32")


def _materialize(x: jax.Array) -> jax.Array:
    """Pin a sampled value to one bit pattern.

    XLA freely *duplicates* producers into every consuming fusion, and the
    recomputed copies of a transcendental chain (the erfinv inside
    ``jax.random.normal``) can round differently per fusion context — the
    stored sample and the sample used in arithmetic silently disagree by
    ulps.  An optimization barrier forces one materialisation that every
    consumer shares, which is what bit-identical host replay requires.
    """
    return jax.lax.optimization_barrier(x)


class DeviceEnsemble:
    """Fused vmapped MLDA stepping for a ``(C,)``-leading chain ensemble.

    Built by :func:`make_device_ensemble`.  Two operating modes:

    * fully fused (``remote_top=False``): every level's density is a pure
      JAX callable; :meth:`advance` runs ``k`` top-level steps for all
      chains as ONE executable (``lax.scan`` over a vmapped step);
    * coupled (``remote_top=True``): the finest level lives behind the
      load balancer.  :meth:`propose` runs the whole coarse subchain
      recursion on device and surfaces ``(C,)`` fine proposals; the host
      evaluates the moved chains' densities (coalesced through the
      balancer's batch pools) and :meth:`accept` folds the results back in.

    Executables are AOT-compiled once per ``(cache_key, padded C[, k])``
    through :class:`repro.swe.solver.AOTBatchCache` (power-of-two chain
    padding, padding chains replicate chain 0 and are sliced off).
    """

    def __init__(
        self,
        log_posteriors: Sequence[Callable],
        subchain_lengths: Sequence[int],
        step_scale,
        *,
        remote_top: bool = False,
        randomize: bool = True,
        cache_key: Sequence = (),
    ) -> None:
        self.n_dev = len(log_posteriors)
        if self.n_dev < 1:
            raise ValueError("need at least one device-resident density")
        self.n_levels = self.n_dev + int(remote_top)
        if len(subchain_lengths) != self.n_levels - 1:
            raise ValueError("need one subchain length per level above 0")
        self.log_posteriors = list(log_posteriors)
        self.subchain_lengths = [int(n) for n in subchain_lengths]
        self.step_scale = jnp.asarray(step_scale, jnp.float32)
        self.remote_top = bool(remote_top)
        self.randomize = bool(randomize)
        self.cache_key = tuple(cache_key)
        self._advance_caches: dict = {}
        self._propose_cache = None
        self._accept_cache = None
        self._chain_fns: dict = {}

    # -- counter-mode draw helpers (single chain; vmapped by the callers) ----
    def _sub_n(self, level: int) -> int:
        """Mean length of the subchain run AT ``level`` (proposing for
        ``level + 1``) — ``subchain_lengths[level]`` in 0-based form."""
        return self.subchain_lengths[level]

    def _t_fixed(self, level: int) -> int:
        n = self._sub_n(level)
        return (2 * n - 1) if (self.randomize and n > 1) else n

    def _draw_length(self, key, counter, level: int):
        """Subchain-length draw for the chain AT ``level``; returns
        ``(length, n_draws_consumed)`` mirroring
        :meth:`MLDASampler._draw_subchain_length` (no draw when the length
        is deterministic)."""
        n = self._sub_n(level)
        if not (self.randomize and n > 1):
            return jnp.asarray(n, jnp.int32), 0
        sub = jax.random.fold_in(key, counter)
        return jax.random.randint(sub, (), 1, 2 * n), 1

    # -- the masked counter-RNG recursion (single chain) ---------------------
    def _chain(self, level: int) -> Callable:
        """``fn(key, theta, logp, counter, counts, length)`` running a
        masked ``t_fixed``-iteration scan of which the first ``length``
        steps are live.  Returns ``(theta, logp, counter, counts)`` with
        ``logp`` the level-``level`` density of the returned state.  Draw
        order per live step replicates the Python machine exactly:

        * level 0: proposal normal, accept uniform (both always);
        * level > 0: length draw for the lower subchain, the subchain's own
          draws, then the accept uniform ONLY if the subchain moved (the
          unmoved shortcut consumes nothing and skips the fine eval).
        """
        fn = self._chain_fns.get(level)
        if fn is not None:
            return fn
        t_fixed = self._t_fixed(level)
        lp = self.log_posteriors

        if level == 0:

            def chain0(key, theta, logp, counter, counts, length):
                def body(carry, i):
                    theta, logp, counter, counts = carry
                    active = i < length
                    z = _materialize(
                        jax.random.normal(
                            jax.random.fold_in(key, counter), theta.shape
                        )
                    )
                    cand = theta + z * self.step_scale
                    logp_cand = lp[0](cand)
                    u = jax.random.uniform(jax.random.fold_in(key, counter + 1))
                    accept = active & (jnp.log(u) < (logp_cand - logp))
                    theta = jnp.where(accept, cand, theta)
                    logp = jnp.where(accept, logp_cand, logp)
                    counter = counter + jnp.where(active, 2, 0)
                    counts = counts.at[0].add(
                        jnp.stack([accept, active, active]).astype(jnp.int32)
                    )
                    return (theta, logp, counter, counts), None

                (theta, logp, counter, counts), _ = jax.lax.scan(
                    body,
                    (theta, logp, counter, counts),
                    jnp.arange(t_fixed, dtype=jnp.int32),
                )
                return theta, logp, counter, counts

            self._chain_fns[level] = chain0
            return chain0

        lower = self._chain(level - 1)

        def chain(key, theta, logp, counter, counts, length):
            # Entry density one level down: the Python machine memoises it,
            # so recomputing here lands on the identical fp32 value.
            logp_low = lp[level - 1](theta)

            def body(carry, i):
                theta, logp, logp_low, counter, counts = carry
                active = i < length
                sub_len, n_draw = self._draw_length(key, counter, level - 1)
                counter = counter + jnp.where(active, n_draw, 0)
                psi, logp_psi_low, counter, counts = lower(
                    key, theta, logp_low, counter, counts,
                    jnp.where(active, sub_len, 0),
                )
                moved = active & jnp.any(psi != theta)
                logp_psi = lp[level](psi)
                u = jax.random.uniform(jax.random.fold_in(key, counter))
                counter = counter + moved.astype(jnp.int32)
                log_alpha = (logp_psi - logp) + (logp_low - logp_psi_low)
                accept = moved & (jnp.log(u) < log_alpha)
                theta = jnp.where(accept, psi, theta)
                logp = jnp.where(accept, logp_psi, logp)
                logp_low = jnp.where(accept, logp_psi_low, logp_low)
                counts = counts.at[level].add(
                    jnp.stack([accept, active, moved]).astype(jnp.int32)
                )
                return (theta, logp, logp_low, counter, counts), None

            (theta, logp, _, counter, counts), _ = jax.lax.scan(
                body,
                (theta, logp, logp_low, counter, counts),
                jnp.arange(t_fixed, dtype=jnp.int32),
            )
            return theta, logp, counter, counts

        self._chain_fns[level] = chain
        return chain

    # -- one top-level transition (single chain, always live) ----------------
    def _top_step(self, key, theta, logp, logp_low, counter, counts):
        """Fully-fused mode only: one MLDA transition at the device top."""
        top = self.n_dev - 1
        lp = self.log_posteriors
        true_ = jnp.asarray(True)
        if self.n_levels == 1:
            z = _materialize(
                jax.random.normal(jax.random.fold_in(key, counter), theta.shape)
            )
            cand = theta + z * self.step_scale
            logp_cand = lp[0](cand)
            u = jax.random.uniform(jax.random.fold_in(key, counter + 1))
            counter = counter + 2
            accept = jnp.log(u) < (logp_cand - logp)
            theta = jnp.where(accept, cand, theta)
            logp = jnp.where(accept, logp_cand, logp)
            counts = counts.at[0].add(
                jnp.stack([accept, true_, true_]).astype(jnp.int32)
            )
            return theta, logp, logp_low, counter, counts
        sub_level = top - 1  # the subchain proposing for the top level
        sub_len, n_draw = self._draw_length(key, counter, sub_level)
        counter = counter + n_draw
        psi, logp_psi_low, counter, counts = self._chain(sub_level)(
            key, theta, logp_low, counter, counts, sub_len
        )
        moved = jnp.any(psi != theta)
        logp_psi = lp[top](psi)
        u = jax.random.uniform(jax.random.fold_in(key, counter))
        counter = counter + moved.astype(jnp.int32)
        log_alpha = (logp_psi - logp) + (logp_low - logp_psi_low)
        accept = moved & (jnp.log(u) < log_alpha)
        theta = jnp.where(accept, psi, theta)
        logp = jnp.where(accept, logp_psi, logp)
        logp_low = jnp.where(accept, logp_psi_low, logp_low)
        counts = counts.at[top].add(
            jnp.stack([accept, true_, moved]).astype(jnp.int32)
        )
        return theta, logp, logp_low, counter, counts

    # -- public API ----------------------------------------------------------
    def init(
        self,
        theta0,
        *,
        seed: int = 0,
        keys: Optional[jax.Array] = None,
        logp0=None,
    ) -> EnsembleState:
        """Start ``C`` chains.  ``theta0`` is ``(C, d)``; chain keys come
        from ``jax.random.split(jax.random.key(seed), C)`` unless given.
        Coupled mode needs ``logp0``: the host-evaluated top densities.
        ``counts[..., 2]`` starts at 1 per level — the initial state
        evaluation each level performs exactly once (further subchain-entry
        evaluations are cache hits in the Python machine)."""
        theta = jnp.asarray(theta0, jnp.float32)
        if theta.ndim != 2:
            raise ValueError(f"theta0 must be (C, d), got {theta.shape}")
        n_chains = theta.shape[0]
        if keys is None:
            keys = jax.random.split(jax.random.key(seed), n_chains)
        keydata = jax.random.key_data(keys)
        if self.remote_top:
            if logp0 is None:
                raise ValueError("coupled mode needs logp0 (host top densities)")
            logp = jnp.asarray(logp0, jnp.float32)
            logp_low = jax.vmap(self.log_posteriors[-1])(theta)
        else:
            logp = jax.vmap(self.log_posteriors[-1])(theta)
            logp_low = (
                jax.vmap(self.log_posteriors[-2])(theta)
                if self.n_dev > 1
                else jnp.zeros(n_chains, jnp.float32)
            )
        counts = (
            jnp.zeros((n_chains, self.n_levels, 3), jnp.int32)
            .at[:, :, 2].set(1)
        )
        return EnsembleState(
            theta=theta,
            logp=logp.astype(jnp.float32),
            logp_low=logp_low.astype(jnp.float32),
            keydata=keydata,
            counter=jnp.zeros(n_chains, jnp.int32),
            counts=counts,
        )

    def advance(self, state: EnsembleState, k: int):
        """Fully-fused mode: ``k`` top-level steps for ALL chains in one
        AOT-compiled launch (``lax.scan`` of the vmapped top step — one
        host sync per call, not per step).  Returns
        ``(state', thetas (C, k, d), logps (C, k))``."""
        if self.remote_top:
            raise RuntimeError(
                "advance() is the fully-fused driver; coupled ensembles "
                "step via propose()/accept()"
            )
        k = int(k)
        if k < 1:
            raise ValueError("k must be >= 1")
        cache = self._advance_caches.get(k)
        if cache is None:
            cache = self._advance_caches[k] = self._make_cache(
                self._advance_fn(k), ("advance", k)
            )
        (state, thetas, logps), n = cache(state)
        state, thetas, logps = jax.tree.map(
            lambda x: x[:n], (state, thetas, logps)
        )
        return state, thetas, logps

    def propose(self, state: EnsembleState):
        """Coupled mode: run every chain's full coarse subchain on device;
        returns ``(state', PendingProposal)``.  The host must evaluate the
        top density of ``pending.psi`` wherever ``pending.moved`` and feed
        the values to :meth:`accept`."""
        if not self.remote_top:
            raise RuntimeError("propose() is for coupled (remote-top) mode")
        if self._propose_cache is None:
            self._propose_cache = self._make_cache(
                self._propose_fn(), ("propose",)
            )
        (state, pending), n = self._propose_cache(state)
        state, pending = jax.tree.map(lambda x: x[:n], (state, pending))
        return state, pending

    def accept(self, state: EnsembleState, pending: PendingProposal, logp_psi):
        """Coupled mode: fold host-evaluated top densities back in.
        ``logp_psi`` is ``(C,)`` (ignored where ``~moved``).  Returns
        ``(state', accepted (C,) bool)``."""
        if not self.remote_top:
            raise RuntimeError("accept() is for coupled (remote-top) mode")
        if self._accept_cache is None:
            self._accept_cache = self._make_cache(
                self._accept_fn(), ("accept",)
            )
        logp_psi = jnp.asarray(logp_psi, jnp.float32)
        (state, accepted), n = self._accept_cache((state, pending, logp_psi))
        state, accepted = jax.tree.map(lambda x: x[:n], (state, accepted))
        return state, accepted

    # -- staged (vmapped, AOT-cached) ensemble programs ----------------------
    def _make_cache(self, fn: Callable, tag: Sequence):
        from repro.swe.solver import AOTBatchCache  # call-time: no cycle

        return AOTBatchCache(
            fn, key=(*self.cache_key, *tag), dtype=None, pad="repeat"
        )

    def _advance_fn(self, k: int) -> Callable:
        def step_chain(keydata, theta, logp, logp_low, counter, counts):
            key = _key_of(keydata)

            def body(carry, _):
                theta, logp, logp_low, counter, counts = carry
                out = self._top_step(key, theta, logp, logp_low, counter, counts)
                return out, (out[0], out[1])

            (theta, logp, logp_low, counter, counts), (thetas, logps) = (
                jax.lax.scan(
                    body, (theta, logp, logp_low, counter, counts), None,
                    length=k,
                )
            )
            return theta, logp, logp_low, counter, counts, thetas, logps

        def advance_all(state: EnsembleState):
            theta, logp, logp_low, counter, counts, thetas, logps = jax.vmap(
                step_chain
            )(
                state.keydata, state.theta, state.logp, state.logp_low,
                state.counter, state.counts,
            )
            new = EnsembleState(
                theta, logp, logp_low, state.keydata, counter, counts
            )
            return new, thetas, logps

        return advance_all

    def _propose_fn(self) -> Callable:
        def propose_chain(keydata, theta, logp_low, counter, counts):
            key = _key_of(keydata)
            sub_level = self.n_dev - 1
            sub_len, n_draw = self._draw_length(key, counter, sub_level)
            counter = counter + n_draw
            psi, logp_psi_low, counter, counts = self._chain(sub_level)(
                key, theta, logp_low, counter, counts, sub_len
            )
            moved = jnp.any(psi != theta)
            u = jax.random.uniform(jax.random.fold_in(key, counter))
            counter = counter + moved.astype(jnp.int32)
            return psi, logp_psi_low, u, moved, counter, counts

        def propose_all(state: EnsembleState):
            psi, logp_psi_low, u, moved, counter, counts = jax.vmap(
                propose_chain
            )(
                state.keydata, state.theta, state.logp_low, state.counter,
                state.counts,
            )
            new = state._replace(counter=counter, counts=counts)
            return new, PendingProposal(psi, logp_psi_low, u, moved)

        return propose_all

    def _accept_fn(self) -> Callable:
        top = self.n_levels - 1

        def accept_chain(theta, logp, logp_low, counts, psi, logp_psi_low,
                         u, moved, logp_psi):
            log_alpha = (logp_psi - logp) + (logp_low - logp_psi_low)
            accept = moved & (jnp.log(u) < log_alpha)
            theta = jnp.where(accept, psi, theta)
            logp = jnp.where(accept, logp_psi, logp)
            logp_low = jnp.where(accept, logp_psi_low, logp_low)
            counts = counts.at[top].add(
                jnp.stack([accept, jnp.asarray(True), moved]).astype(jnp.int32)
            )
            return theta, logp, logp_low, counts, accept

        def accept_all(args):
            state, pending, logp_psi = args
            theta, logp, logp_low, counts, accepted = jax.vmap(accept_chain)(
                state.theta, state.logp, state.logp_low, state.counts,
                pending.psi, pending.logp_psi_low, pending.u, pending.moved,
                logp_psi,
            )
            new = EnsembleState(
                theta, logp, logp_low, state.keydata, state.counter, counts
            )
            return new, accepted

        return accept_all


def make_device_ensemble(
    log_posteriors: Sequence[Callable],
    subchain_lengths: Sequence[int],
    step_scale,
    *,
    remote_top: bool = False,
    randomize: bool = True,
    cache_key: Sequence = (),
) -> DeviceEnsemble:
    """Build a :class:`DeviceEnsemble`.

    ``log_posteriors`` are the *device-resident* densities coarse -> fine
    (pure JAX callables on a single ``(d,)`` theta).  With
    ``remote_top=True`` the hierarchy has one more level on top whose
    density lives behind the balancer; ``subchain_lengths`` always covers
    the full hierarchy (one entry per level above 0).  ``step_scale`` is
    the level-0 random-walk scale (scalar or per-dim), quantised to fp32 —
    pair host chains with :class:`DeviceMatchedRandomWalk` +
    :class:`CounterStream` for bit-identical replay.
    """
    return DeviceEnsemble(
        log_posteriors, subchain_lengths, step_scale,
        remote_top=remote_top, randomize=randomize, cache_key=cache_key,
    )


# ---------------------------------------------------------------------------
# Host-side equivalence shims: replay the device RNG stream / arithmetic
# through the Python step machine (tests + step-machine baselines).
# ---------------------------------------------------------------------------
class CounterStream:
    """``np.random.Generator``-shaped stream in device counter mode.

    Every draw is ``jax.random.fold_in(chain_key, counter)`` with the
    counter incremented per draw — the exact stream the fused kernel
    consumes, so an :class:`repro.core.mlda.MLDASampler` driven by this
    object visits bit-identical states.  Implements only what the MLDA
    machine uses: ``normal(size=)``, ``uniform()``, ``integers(lo, hi)``.
    """

    def __init__(self, key, counter: int = 0) -> None:
        self.key = key  # a typed jax PRNG key (jax.random.key / split row)
        self.counter = int(counter)

    def _sub(self):
        sub = jax.random.fold_in(self.key, self.counter)
        self.counter += 1
        return sub

    def normal(self, size=None):
        shape = (size,) if isinstance(size, int) else tuple(size or ())
        out = np.asarray(jax.random.normal(self._sub(), shape))
        return out if size is not None else float(out)

    def uniform(self) -> float:
        return float(jax.random.uniform(self._sub()))

    def integers(self, low, high=None) -> int:
        if high is None:
            low, high = 0, low
        return int(jax.random.randint(self._sub(), (), int(low), int(high)))


@dataclass
class DeviceMatchedRandomWalk(Proposal):
    """Random walk reproducing the kernel's candidate arithmetic bit-exactly.

    Two deltas vs :class:`repro.core.mh.GaussianRandomWalk`: (1) the state
    is quantised to fp32 (the f64-accumulating host chain would drift from
    the device chain after the first accepted step); (2) the update is
    computed as a *fused* multiply-add — XLA's CPU/TPU backends contract
    ``theta + z * scale`` into one FMA, so the host emulates it via exact
    f64 products (a 24-bit x 24-bit product is exact in f64) with a single
    final rounding to fp32.
    """

    scale: Any = 1.0

    def sample(self, rng, theta):
        theta64 = np.asarray(theta, np.float32).astype(np.float64)
        z = np.asarray(rng.normal(size=theta64.shape), np.float32)
        s = np.asarray(self.scale, np.float32).astype(np.float64)
        return (theta64 + z.astype(np.float64) * s).astype(np.float32)
