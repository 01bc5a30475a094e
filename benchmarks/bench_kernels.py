"""Kernel-plane benchmarks.

Wall-clock here is the **pure-jnp reference on CPU** (Pallas interpret mode
measures Python, not TPU): the numbers are throughput sanity checks for the
paper-technique ops (dot-seen filtering ~ the read-fold hot loop, clock
joins ~ delta apply).  The TPU-side story for each Pallas kernel is static:
VMEM working set + arithmetic intensity, reported per kernel from its
BlockSpec geometry (see EXPERIMENTS.md §Roofline / kernels table).
"""
from __future__ import annotations

import time
from typing import List

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import vclock
from repro.kernels.clock_ops import ref as clock_ref
from repro.kernels.dot_seen.ref import dot_seen_ref


def _time(fn, *args, iters=20):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _rand_runs(rng, n_actors: int, n_runs: int):
    """Random canonical (sorted, disjoint, non-adjacent) interval arrays."""
    gaps = rng.integers(2, 20, (n_actors, n_runs))
    lens = rng.integers(0, 63, (n_actors, n_runs))
    ends = np.cumsum(gaps + lens, axis=1)
    starts = ends - lens
    return (jnp.asarray(starts, jnp.int32), jnp.asarray(ends, jnp.int32),
            int(ends.max()))


def main(quick=False) -> List[str]:
    rows = []
    rng = np.random.default_rng(0)
    n_dots = 1 << (16 if quick else 20)
    A, R = 64, 256
    starts, ends, maxc = _rand_runs(rng, A, R)
    actors = jnp.asarray(rng.integers(0, A, n_dots), jnp.int32)
    counters = jnp.asarray(rng.integers(1, maxc, n_dots), jnp.int32)
    f = jax.jit(dot_seen_ref)
    dt = _time(f, starts, ends, actors, counters)
    rows.append(f"kernel/dot_seen_ref/{n_dots},{dt * 1e6:.1f},"
                f"{n_dots / dt / 1e6:.1f}Mdots/s")

    # runs are causal metadata: 128 runs/actor is already a heavily churned
    # clock.  The boundary sweep is O(P^2) per actor row (P = Ra + Rb
    # candidate edges), so throughput is reported in run-merges/s.
    AJ, RJ = 512, 128
    a_s, a_e, _ = _rand_runs(rng, AJ, RJ)
    b_s, b_e, _ = _rand_runs(rng, AJ, RJ)
    fj = jax.jit(clock_ref.join_ref)
    dt = _time(fj, a_s, a_e, b_s, b_e)
    rows.append(f"kernel/clock_join/{AJ}x{RJ}runs,{dt * 1e6:.1f},"
                f"{AJ * RJ * 2 / dt / 1e6:.1f}Mruns/s")

    fp = jax.jit(clock_ref.popcount_ref)
    dt = _time(fp, a_s, a_e)
    rows.append(f"kernel/clock_popcount/{AJ}x{RJ}runs,{dt * 1e6:.1f},"
                f"{a_s.size * 4 * 2 / 1e9 / dt:.1f}GB/s")

    # static TPU-side kernel geometry (BlockSpec working sets)
    rows.append("kernel/flash_attention/vmem,0,"
                "BQ=BKV=128xD<=256: qkv 384KiB + acc 128KiB < 1MiB VMEM; "
                "AI=O(BKV) flops/byte -> compute-bound on MXU")
    rows.append("kernel/decode_attention/vmem,0,"
                "group-padded rows x BKV=256: streams cache once; "
                "AI~2 flops/byte -> HBM-bound (roofline: memory term)")
    rows.append("kernel/mamba_scan/vmem,0,"
                "state 512x16 f32 = 32KiB resident; one pass over x/dt/B/C")
    rows.append("kernel/dot_seen/vmem,0,"
                "clock streamed in [A x 512]-run tiles (~6.6MiB per step "
                "@ A=8 whatever R); one-hot MXU row gather + broadcast "
                "interval test, dots in 1024-blocks")
    return rows


if __name__ == "__main__":
    for row in main():
        print(row)
