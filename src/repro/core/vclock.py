"""Dense JAX logical clocks — the TPU-native form of BaseVV + DotCloud.

The paper's clocks are sparse maps; their hot operations (dot-seen filtering
of element-key streams, clock joins, tombstone subtraction) are the write
and read path of every bigset op.  On TPU we hold a *dense interval* clock
per actor universe:

* ``starts : int32[A, R]`` — per-actor run start counters,
* ``ends   : int32[A, R]`` — per-actor run end counters (inclusive).

Row ``a`` holds the actor's seen events as sorted, disjoint, coalesced
``(lo, hi)`` runs — the base VV is simply the first run when it starts at 1.
Empty slots are the sentinel ``(1, 0)`` (``lo > hi``), which no membership
test can hit.  This mirrors :class:`repro.core.clock.Clock`'s run cloud:
cost is O(interval runs) — causal metadata — with **no window cap** (the old
``uint32`` bitmap silently could not represent dots beyond its
``window_events`` spread at all, and subtraction required matching origins).

The lattice ops become data-parallel interval merges over fixed shapes:

    join      = run union            (set-clock ⊔ delta)
    subtract  = run difference       (tombstone shrink, §4.3.3) — origin-free
    intersect = run intersection     (tombstone ∩ raw trim)
    seen      = any(lo ≤ c ≤ hi)     (Algorithms 1 & 2)
    popcount  = Σ (hi - lo + 1)      (events per actor)

The merges use a boundary sweep: a counter ``p`` starts an output run iff it
is live under the op's predicate and ``p - 1`` is not; ``p`` ends one iff it
is live and ``p + 1`` is not.  Candidate boundaries come only from input run
edges, so the sweep is O(P²) dense compares over P = Ra + Rb candidates —
fixed-shape, branch-free work that maps straight onto the VPU.

``dots_seen`` — the per-element-key filter applied millions of times during
a read fold — is the Pallas kernel in :mod:`repro.kernels.dot_seen`; the
per-dot row gather is expressed as one-hot matmuls so it runs on the MXU
instead of a scatter/gather unit TPUs don't have.  This module is the
pure-jnp oracle (``ref``) for those kernels and the conversion layer to/from
the sparse :class:`repro.core.clock.Clock`.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .clock import Clock

_INT32_MAX = np.int32(2**31 - 1)


class DenseClock(NamedTuple):
    starts: jax.Array  # int32[A, R] (empty slot: starts=1, ends=0)
    ends: jax.Array    # int32[A, R]

    @property
    def n_actors(self) -> int:
        return self.starts.shape[0]

    @property
    def n_runs(self) -> int:
        return self.starts.shape[1]


def zero(n_actors: int, n_runs: int = 1) -> DenseClock:
    return DenseClock(
        jnp.ones((n_actors, n_runs), jnp.int32),
        jnp.zeros((n_actors, n_runs), jnp.int32),
    )


# ------------------------------------------------------------------- seen
def dots_seen(clock: DenseClock, actors: jax.Array, counters: jax.Array) -> jax.Array:
    """Vectorised Algorithm-1/2 membership test.

    actors : int32[N] (indices into the actor universe)
    counters : int32[N] (event numbers, 1-based)
    returns bool[N]

    A dot is seen iff some run of its actor's row contains its counter —
    a broadcast interval test over all R runs, no window cap.
    """
    s = clock.starts[actors]                     # [N, R]
    e = clock.ends[actors]                       # [N, R]
    c = counters[:, None]                        # [N, 1]
    return jnp.any((s <= c) & (c <= e), axis=1)


# ------------------------------------------------------------------ lattice
def _require_same_universe(a: DenseClock, b: DenseClock) -> None:
    if a.starts.shape[0] != b.starts.shape[0]:
        raise ValueError("dense clocks must share the actor universe")


def _interval_merge(a_s, a_e, b_s, b_e, mode: str):
    """Boundary-sweep run merge — shared math for join/subtract/intersect.

    Inputs are int32[A, Ra] / int32[A, Rb] run arrays; output is the
    *unsorted* int32[A, Ra+Rb] run arrays of the result (empty slots
    ``(1, 0)``).  ``mode``: ``"or"`` (union), ``"andnot"`` (difference),
    ``"and"`` (intersection).

    A counter ``p`` is *live* when the mode's predicate over (in-A, in-B)
    holds.  Output runs start at live points whose predecessor is dead and
    end at live points whose successor is dead; every such boundary is an
    edge of an input run (shifted by one for the B side of ``andnot``), so
    the candidate set has fixed size P = Ra + Rb.
    """
    a_valid = a_s <= a_e
    b_valid = b_s <= b_e

    def in_a(x):  # x: int32[A, P] -> bool[A, P]
        return jnp.any(
            (a_s[:, None, :] <= x[:, :, None]) & (x[:, :, None] <= a_e[:, None, :]),
            axis=-1,
        )

    def in_b(x):
        return jnp.any(
            (b_s[:, None, :] <= x[:, :, None]) & (x[:, :, None] <= b_e[:, None, :]),
            axis=-1,
        )

    if mode == "or":
        def live(x):
            return in_a(x) | in_b(x)
        cand_s = jnp.concatenate([a_s, b_s], axis=1)
        s_valid = jnp.concatenate([a_valid, b_valid], axis=1)
        cand_e = jnp.concatenate([a_e, b_e], axis=1)
        e_valid = s_valid
    elif mode == "andnot":
        def live(x):
            return in_a(x) & ~in_b(x)
        # a difference run starts at an A start or just after a B end,
        # and ends at an A end or just before a B start
        cand_s = jnp.concatenate([a_s, b_e + 1], axis=1)
        s_valid = jnp.concatenate([a_valid, b_valid], axis=1)
        cand_e = jnp.concatenate([a_e, b_s - 1], axis=1)
        e_valid = s_valid
    elif mode == "and":
        def live(x):
            return in_a(x) & in_b(x)
        cand_s = jnp.concatenate([a_s, b_s], axis=1)
        s_valid = jnp.concatenate([a_valid, b_valid], axis=1)
        cand_e = jnp.concatenate([a_e, b_e], axis=1)
        e_valid = s_valid
    else:  # pragma: no cover
        raise ValueError(f"unknown merge mode {mode!r}")

    is_start = s_valid & live(cand_s) & ~live(cand_s - 1)
    # two candidates can carry the same start value (e.g. identical runs in
    # both inputs under "or") — keep only the first occurrence per row
    p = cand_s.shape[1]
    same = cand_s[:, :, None] == cand_s[:, None, :]            # [A, P, P]
    earlier = jnp.tril(jnp.ones((p, p), bool), k=-1)           # [P, P] q < p
    dup = jnp.any(same & earlier[None, :, :] & is_start[:, None, :], axis=-1)
    is_start = is_start & ~dup

    is_end = e_valid & live(cand_e) & ~live(cand_e + 1)
    # each output run ends at the smallest end-boundary >= its start
    reach = is_end[:, None, :] & (cand_e[:, None, :] >= cand_s[:, :, None])
    ends_for = jnp.min(
        jnp.where(reach, cand_e[:, None, :], _INT32_MAX), axis=-1)

    out_s = jnp.where(is_start, cand_s, 1).astype(jnp.int32)
    out_e = jnp.where(is_start, ends_for, 0).astype(jnp.int32)
    return out_s, out_e


def sort_runs(starts: jax.Array, ends: jax.Array):
    """Canonicalise run arrays: sort rows by start, empties ``(1, 0)`` last."""
    valid = starts <= ends
    key = jnp.where(valid, starts, _INT32_MAX)
    order = jnp.argsort(key, axis=1)
    s = jnp.take_along_axis(starts, order, axis=1)
    e = jnp.take_along_axis(ends, order, axis=1)
    ok = s <= e
    return jnp.where(ok, s, 1), jnp.where(ok, e, 0)


def join(a: DenseClock, b: DenseClock) -> DenseClock:
    """⊔ of two dense clocks (run union) — no alignment requirements."""
    _require_same_universe(a, b)
    s, e = _interval_merge(a.starts, a.ends, b.starts, b.ends, "or")
    return DenseClock(*sort_runs(s, e))


def subtract(a: DenseClock, b: DenseClock) -> DenseClock:
    """Remove b's events from a (tombstone shrink, §4.3.3).

    Origin-free: runs below either clock's contiguous horizon subtract the
    same as any other runs (the old bitmap form required matching origins
    and silently could not subtract events at/below them).
    """
    _require_same_universe(a, b)
    s, e = _interval_merge(a.starts, a.ends, b.starts, b.ends, "andnot")
    return DenseClock(*sort_runs(s, e))


def intersect(a: DenseClock, b: DenseClock) -> DenseClock:
    """Events seen by both clocks (run intersection)."""
    _require_same_universe(a, b)
    s, e = _interval_merge(a.starts, a.ends, b.starts, b.ends, "and")
    return DenseClock(*sort_runs(s, e))


def add_dots(clock: DenseClock, actors: jax.Array, counters: jax.Array) -> DenseClock:
    """Observe a batch of dots (delta apply) — one run build + one merge.

    Sorts the dots, detects run breaks, segment-reduces each run's bounds,
    scatters the runs into per-actor rows and unions them with the clock.
    No per-bit planes, no scatter-OR emulation: duplicate dots land in the
    same run and adjacent counters coalesce before the merge.
    """
    n = int(actors.shape[0])
    if n == 0:
        return clock
    n_a = clock.n_actors
    order = jnp.lexsort((counters, actors))
    a = jnp.asarray(actors, jnp.int32)[order]
    c = jnp.asarray(counters, jnp.int32)[order]
    prev_a = jnp.concatenate([a[:1] - 1, a[:-1]])
    prev_c = jnp.concatenate([c[:1], c[:-1]])
    new_run = (a != prev_a) | (c > prev_c + 1)
    gid = jnp.cumsum(new_run.astype(jnp.int32)) - 1             # [n]
    run_lo = jax.ops.segment_min(c, gid, num_segments=n)
    run_hi = jax.ops.segment_max(c, gid, num_segments=n)
    run_actor = jax.ops.segment_max(a, gid, num_segments=n)
    run_ids = jnp.arange(n, dtype=jnp.int32)
    valid = run_ids <= gid[-1]
    run_actor = jnp.where(valid, run_actor, n_a)                # drop pads
    # rank of each run within its actor row (runs are actor-grouped)
    first = jax.ops.segment_min(run_ids, run_actor, num_segments=n_a + 1)
    rank = run_ids - first[run_actor]
    delta_s = jnp.ones((n_a, n), jnp.int32)
    delta_e = jnp.zeros((n_a, n), jnp.int32)
    delta_s = delta_s.at[run_actor, rank].set(run_lo, mode="drop")
    delta_e = delta_e.at[run_actor, rank].set(run_hi, mode="drop")
    return join(clock, DenseClock(delta_s, delta_e))


def compact(clock: DenseClock) -> DenseClock:
    """Trim trailing all-empty run columns (host-side width reduction).

    Merges widen arrays to Ra + Rb; after coalescing most columns are the
    empty sentinel.  Call between chained merges to keep widths O(runs).
    """
    s = np.asarray(clock.starts)
    e = np.asarray(clock.ends)
    used = (s <= e).any(axis=0)
    width = max(1, int(used.nonzero()[0].max()) + 1 if used.any() else 1)
    return DenseClock(jnp.asarray(s[:, :width]), jnp.asarray(e[:, :width]))


def popcount(clock: DenseClock) -> jax.Array:
    """Events per actor — Σ (hi - lo + 1) over valid runs (int32[A])."""
    return jnp.maximum(clock.ends - clock.starts + 1, 0).sum(axis=1)


def base_vv(clock: DenseClock) -> jax.Array:
    """Effective version vector: the contiguous horizon per actor.

    Requires canonical (sorted) rows — true for anything built by
    :func:`from_clock` or returned by the merge ops.
    """
    return jnp.where(clock.starts[:, 0] == 1, clock.ends[:, 0], 0)


# ------------------------------------------------------------- conversions
def from_clock(
    clock: Clock, actor_index: Dict[object, int], n_actors: int,
    n_runs: int | None = None,
) -> DenseClock:
    """Sparse → dense: O(runs), one row slot per interval run.

    ``n_runs`` pads the run axis to a fixed width (for shape-stable jit);
    defaults to the widest row.  Raises if a row needs more than ``n_runs``.
    The arrays are host ``numpy`` arrays: the caller decides when and
    where they are copied to a device (every op here also takes them).
    """
    rows: Dict[int, list] = {}
    for a, lo, hi in clock.iter_runs():
        rows.setdefault(actor_index[a], []).append((lo, hi))
    widest = max((len(r) for r in rows.values()), default=0)
    width = max(1, widest) if n_runs is None else n_runs
    if widest > width:
        raise ValueError(
            f"clock has {widest} runs in a row; n_runs={width} too narrow")
    starts = np.ones((n_actors, width), np.int32)
    ends = np.zeros((n_actors, width), np.int32)
    for i, rs in rows.items():
        for k, (lo, hi) in enumerate(rs):
            starts[i, k] = lo
            ends[i, k] = hi
    return DenseClock(starts, ends)


def to_clock(clock: DenseClock, actors: Sequence[object]) -> Clock:
    """Dense → sparse (normalised BaseVV + run cloud)."""
    s = np.asarray(clock.starts)
    e = np.asarray(clock.ends)
    runs: Dict[object, list] = {}
    for i, a in enumerate(actors):
        rs = [(int(lo), int(hi)) for lo, hi in zip(s[i], e[i]) if lo <= hi]
        if rs:
            runs[a] = rs
    return Clock(runs=runs)
