"""Vectorised dot-visibility filtering for element-key streams.

The hot loop of every bigset read is "has the set-tombstone seen this dot?"
— executed once per element-key.  The scalar path does a Python dict probe
per dot; this module batches a whole scan chunk into dense ``(actors,
counters)`` ``int32`` arrays and dispatches the ``kernels/dot_seen`` kernel
(Pallas on TPU, pure-jnp reference elsewhere) so visibility for thousands of
keys resolves in one device call.

The tombstone is converted into the dense
:class:`~repro.core.vclock.DenseClock` *interval* form (per-actor
``(lo, hi)`` run arrays), held on the device by a :class:`DenseTombstone`;
every chunk of every stream that reads the same tombstone ``Clock``
object reuses it (the executor keeps one per vnode and set).  The build is
O(interval runs) — causal metadata — with **no window cap**: the old
bitmap form had to fall back to scalar probes beyond a fixed per-actor
spread, but a run covers any span at constant cost.  Dots by actors the
tombstone has never heard of are unseen by definition and route to the
sentinel counter ``0``, which no 1-based run can contain.  Every shape
is bucketed so jit compiles a handful of programs, not one per chunk
length or per remove: batches pad to a multiple of :data:`PAD_BUCKET`,
actors to a multiple of :data:`ACTOR_BUCKET`, and runs to a power of two
no narrower than the kernel's run tile.

Spans (when the query's tracer is on): ``query.visibility_build`` around
a stream's visibility set-up (the executor's memo lookup, and any build),
with ``kernel.h2d`` around a build's copy of the tombstone to the device;
per chunk ``query.pack`` (dots to arrays) or ``query.host_seen``
(a chunk under :data:`MIN_BATCH`), ``kernel.dot_seen`` around the launch
and ``kernel.mask_sync`` while the host waits for the mask.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.clock import Clock
from ..core.dots import Dot
from ..core.vclock import DenseClock, from_clock
from ..kernels.dot_seen.kernel import RUN_TILE
from ..obs.trace import NO_SPAN, NULL_TRACER, Tracer

# Chunks smaller than this aren't worth a device dispatch.
MIN_BATCH = 32
# Pad batches up to a multiple of this so jit sees few distinct shapes.
PAD_BUCKET = 512
# Pad the actor axis to a multiple of this (the TPU's sublane count).
ACTOR_BUCKET = 8
# The kernel gathers counters through f32, which is exact below 2**24.
MAX_COUNTER = 2**24 - 1


def dense_shape(tombstone: Clock) -> Tuple[int, int]:
    """``(actors, widest row's runs)`` of a tombstone, before bucketing."""
    rows: Dict[object, int] = {}
    for a, _lo, _hi in tombstone.iter_runs():
        rows[a] = rows.get(a, 0) + 1
    return len(rows), max(rows.values(), default=0)


def bucket_shape(n_actors: int, n_runs: int) -> Tuple[int, int]:
    """The ``(A, R)`` the kernel is compiled for: A up to a multiple of
    :data:`ACTOR_BUCKET`, R up to a power of two of at least
    :data:`RUN_TILE` — a growing tombstone recompiles once per doubling."""
    a = -(-max(n_actors, 1) // ACTOR_BUCKET) * ACTOR_BUCKET
    r = RUN_TILE
    while r < n_runs:
        r *= 2
    return a, r


class DenseTombstone:
    """A tombstone as the kernel sees it: its actors' row index and its
    runs as a bucketed :class:`~repro.core.vclock.DenseClock` whose arrays
    are already on the device.

    Immutable, and a pure function of the value of ``tombstone`` (actors
    sorted by ``repr``, :func:`bucket_shape`, ``from_clock``).  Clocks are
    immutable too, so an entry serves every read that returns the very
    ``Clock`` object it was built from, and no other.
    """

    __slots__ = ("tombstone", "actor_index", "dense")

    def __init__(self, tombstone: Clock, tracer: Tracer = NULL_TRACER):
        actors = sorted(tombstone.actors(), key=repr)
        self.actor_index: Dict[object, int] = {
            a: i for i, a in enumerate(actors)}
        n_actors, n_runs = bucket_shape(*dense_shape(tombstone))
        host = from_clock(
            tombstone, self.actor_index, n_actors, n_runs=n_runs)
        with tracer.span("kernel.h2d"):
            self.dense = DenseClock(
                jnp.asarray(host.starts), jnp.asarray(host.ends))
        self.tombstone = tombstone


class BatchVisibility:
    """Batched ``tombstone.seen(dot)`` over chunks of a scan stream.

    ``dense`` is the tombstone's :class:`DenseTombstone` where the caller
    keeps one (it must be built from this very ``tombstone``); without it
    a non-empty tombstone is densified here.
    """

    def __init__(
        self,
        tombstone: Clock,
        *,
        dense: Optional[DenseTombstone] = None,
        use_pallas: Optional[bool] = None,
        interpret: Optional[bool] = None,
        min_batch: int = MIN_BATCH,
        stats=None,
        tracer: Tracer = NULL_TRACER,
    ):
        self.tombstone = tombstone
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.min_batch = min_batch
        # per-query launch accounting (QueryStats.kernel_launches/_rows,
        # tombstone_runs): the cross-query micro-batcher's baseline
        self.stats = stats
        self.tracer = tracer
        self.dense = None  # the tombstone as the kernel sees it (bucketed)
        self._actor_index: Dict[object, int] = {}
        # counters are 1-based, so 0 is unseen by every run — the routing
        # target for padding and for actors the tombstone never heard of
        self._sentinel = 0

        if tombstone.is_zero():
            self._mode = "empty"
            return
        self._mode = "dense"
        if dense is None:
            with tracer.span("query.visibility_build"):
                dense = DenseTombstone(tombstone, tracer)
        elif dense.tombstone is not tombstone:
            raise ValueError(
                "a DenseTombstone serves only the Clock it was built from")
        self.dense = dense.dense
        self._actor_index = dense.actor_index
        if stats is not None:
            stats.tombstone_runs += tombstone.n_runs()

    # ------------------------------------------------------------------ api
    def seen_mask(self, dots: Sequence[Dot]) -> np.ndarray:
        """bool[N] — which of ``dots`` has the tombstone seen (i.e. are dead)?"""
        n = len(dots)
        if n == 0:
            return np.zeros((0,), bool)
        if self._mode == "empty":
            return np.zeros((n,), bool)
        tr = self.tracer
        if n < self.min_batch:
            with tr.span("query.host_seen") if tr.enabled else NO_SPAN:
                return self.seen_scalar(dots)
        with tr.span("query.pack") if tr.enabled else NO_SPAN:
            actors, counters = self._pack(dots)
        if self.stats is not None:
            self.stats.kernel_launches += 1
            self.stats.kernel_rows += n
        from ..kernels.dot_seen import dot_seen

        # A and R name the compiled shape, N the padded batch
        with (tr.span("kernel.dot_seen", A=self.dense.n_actors,
                      R=self.dense.n_runs, N=len(actors))
              if tr.enabled else NO_SPAN):
            mask = dot_seen(
                self.dense, actors, counters,
                use_pallas=self.use_pallas, interpret=self.interpret,
            )
        with tr.span("kernel.mask_sync") if tr.enabled else NO_SPAN:
            return np.asarray(mask)[:n]

    def _pack(self, dots: Sequence[Dot]) -> Tuple[np.ndarray, np.ndarray]:
        """The chunk's dots as ``(actors, counters)`` ``int32`` arrays,
        padded to a multiple of :data:`PAD_BUCKET` with the sentinel."""
        n = len(dots)
        idx = self._actor_index
        actors = np.empty((n,), np.int32)
        counters = np.empty((n,), np.int32)
        for i, d in enumerate(dots):
            j = idx.get(d.actor, -1)
            if j < 0:
                # unknown actor: route to slot 0 with the sentinel counter,
                # which the kernel reports unseen
                actors[i] = 0
                counters[i] = self._sentinel
            else:
                if d.counter > MAX_COUNTER:
                    raise ValueError(
                        f"dot counter {d.counter} exceeds {MAX_COUNTER}, "
                        "the largest the kernel's f32 gather holds exactly")
                actors[i] = j
                counters[i] = d.counter
        pad = (-n) % PAD_BUCKET
        if pad:
            actors = np.pad(actors, (0, pad))
            counters = np.pad(
                counters, (0, pad), constant_values=self._sentinel)
        return actors, counters

    def seen_scalar(self, dots: Sequence[Dot]) -> np.ndarray:
        """Scalar oracle (for tests / tiny batches)."""
        ts = self.tombstone
        return np.fromiter(
            (ts.seen(d) for d in dots), bool, count=len(dots))
