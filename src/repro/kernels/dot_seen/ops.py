"""Jit'd public wrapper for the dot-seen kernel.

Dispatch is chosen by backend, once per process: on a ``tpu`` backend the
compiled Pallas kernel, anywhere else the pure-jnp reference.  Tests that
want the Pallas interpreter ask for it (``use_pallas=True,
interpret=True``).  The bigset read fold and delta-batch dedup call this
with the tombstone / set-clock in dense *interval* form: per-actor
``(lo, hi)`` run arrays (``DenseClock.starts`` / ``.ends``), O(interval
runs) with no window cap.

Every call is tallied in the process-wide :data:`DISPATCHES` ledger
(launch count + rows dispatched, padding included).  That ledger is the
measured baseline for the ROADMAP cross-query micro-batcher: today 1000
concurrent small queries pay 1000 launches over tiny arrays, and the only
honest way to claim a coalescer wins is to watch ``launches`` fall while
``rows`` holds.  ``benchmarks/bench_serve.py`` reports it as amortized
launches/query; the metrics registry lifts it via
:func:`repro.obs.metrics.lift_dispatch_stats`.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ...core.vclock import DenseClock
from .kernel import dot_seen_pallas
from .ref import dot_seen_ref


@dataclass
class DispatchStats:
    """Kernel-launch ledger: device calls and rows (dots) they covered."""

    launches: int = 0       # dot_seen invocations (one device dispatch each)
    rows: int = 0           # total rows dispatched, padding included
    pallas_launches: int = 0  # subset of launches routed to the Pallas kernel
    interpreted: int = 0    # subset of pallas_launches run by the interpreter

    def snapshot(self) -> "DispatchStats":
        return DispatchStats(**vars(self))

    def delta(self, since: "DispatchStats") -> "DispatchStats":
        return DispatchStats(
            **{k: getattr(self, k) - getattr(since, k) for k in vars(self)})


DISPATCHES = DispatchStats()


@functools.cache
def on_tpu() -> bool:
    """Does this process dispatch to a TPU?  (Decided once, on first use.)"""
    return jax.default_backend() == "tpu"


def dot_seen(
    clock: DenseClock,
    actors: jax.Array,
    counters: jax.Array,
    *,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """bool[N] — which dots has ``clock`` seen?"""
    actors = jnp.asarray(actors, jnp.int32)
    counters = jnp.asarray(counters, jnp.int32)
    DISPATCHES.launches += 1
    DISPATCHES.rows += int(actors.shape[0])
    if use_pallas is None:
        use_pallas = on_tpu()
    if use_pallas:
        if interpret is None:
            interpret = not on_tpu()
        DISPATCHES.pallas_launches += 1
        DISPATCHES.interpreted += int(interpret)
        return dot_seen_pallas(
            clock.starts, clock.ends, actors, counters, interpret=interpret
        )
    return dot_seen_ref(clock.starts, clock.ends, actors, counters)
