"""Pallas TPU kernel: batched dot-seen test against a dense interval clock.

TPUs have no efficient scatter/gather unit, so the per-dot row lookups
``starts[actor, :]`` / ``ends[actor, :]`` are expressed as **one-hot
contractions on the MXU**:

* ``starts[actor, :]`` → onehot(actors, A) @ starts          [BN, RT]
* ``ends[actor, :]``   → onehot(actors, A) @ ends            [BN, RT]

Run bounds and counters are below 2²⁴, so they are exact in f32, and the
contractions ask for ``Precision.HIGHEST``: the MXU's default single bf16
pass would round them.  The membership test ``any(lo ≤ c ≤ hi)`` is then
a VPU broadcast-compare over the tile's run columns.

The grid is ``(dot blocks, run tiles)``.  Dot blocks are independent
(``"parallel"``); run tiles are ``"arbitrary"``: each ORs its hits into
the dot block's resident output, so a tombstone of any width streams
through VMEM one ``[A, RT]`` tile at a time.  A run axis wider than one
tile is padded to a multiple of :data:`RUN_TILE` with empty ``(1, 0)``
slots, which never match.

VMEM per grid step (A=8, RT=512, BN=1024), double-buffered inputs:
  runs 2·2·8·512·4B = 64 KiB, onehotA 1024·128·4 = 512 KiB (lane-padded),
  rows + compare 3·1024·512·4 = 6 MiB  →  ~6.6 MiB, whatever R is,
  under the 16 MiB scoped-VMEM default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 1024
RUN_TILE = 512


def _kernel(starts_ref, ends_ref, actors_ref, counters_ref, out_ref,
            *, n_actors: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    actors = actors_ref[...]                            # int32[BN]
    counters = counters_ref[...]                        # int32[BN]
    bn = actors.shape[0]

    # --- gather the actor's run row via one-hot matmul (f32-exact: < 2^24)
    onehot_a = (actors[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (bn, n_actors), 1)).astype(jnp.float32)      # [BN, A]
    hi = jax.lax.Precision.HIGHEST
    rows_s = jnp.dot(onehot_a, starts_ref[...].astype(jnp.float32),
                     precision=hi, preferred_element_type=jnp.float32)
    rows_e = jnp.dot(onehot_a, ends_ref[...].astype(jnp.float32),
                     precision=hi, preferred_element_type=jnp.float32)

    # --- interval membership: empty slots are (1, 0), which never match
    c = counters[:, None].astype(jnp.float32)                   # [BN, 1]
    hit = (rows_s <= c) & (c <= rows_e)                         # [BN, RT]
    out_ref[...] |= jnp.any(hit, axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def dot_seen_pallas(
    starts: jax.Array,    # int32[A, R]
    ends: jax.Array,      # int32[A, R]
    actors: jax.Array,    # int32[N]
    counters: jax.Array,  # int32[N]
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    n = actors.shape[0]
    n_actors, n_runs = starts.shape

    pad = (-n) % block_n
    if pad:
        actors = jnp.pad(actors, (0, pad))
        counters = jnp.pad(counters, (0, pad))
    n_pad = actors.shape[0]

    run_tile = min(n_runs, RUN_TILE)  # a narrow clock is one full-dim tile
    if n_runs % run_tile:
        extra = run_tile - n_runs % run_tile
        starts = jnp.pad(starts, ((0, 0), (0, extra)), constant_values=1)
        ends = jnp.pad(ends, ((0, 0), (0, extra)), constant_values=0)
        n_runs += extra

    grid = (n_pad // block_n, n_runs // run_tile)
    out = pl.pallas_call(
        functools.partial(_kernel, n_actors=n_actors),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_actors, run_tile), lambda i, j: (0, j)),  # starts
            pl.BlockSpec((n_actors, run_tile), lambda i, j: (0, j)),  # ends
            pl.BlockSpec((block_n,), lambda i, j: (i,)),              # actors
            pl.BlockSpec((block_n,), lambda i, j: (i,)),              # counters
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(starts, ends, actors, counters)
    return out[:n].astype(bool)
