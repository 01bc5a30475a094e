"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle,
swept over shapes and dtypes, plus vclock dense/sparse agreement."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st
from numpy.testing import assert_allclose

from repro.core import vclock
from repro.core.clock import Clock
from repro.core.dots import Dot
from repro.kernels.decode_attention import decode_attention_pallas, decode_attention_ref
from repro.kernels.dot_seen import dot_seen_pallas, dot_seen_ref
from repro.kernels.flash_attention import attention_ref, flash_attention_pallas
from repro.kernels.dot_seen.kernel import RUN_TILE
from repro.kernels.mamba_scan import mamba_scan_pallas, mamba_scan_ref, mamba_step_ref
from repro.query.batch import (ACTOR_BUCKET, MAX_COUNTER, BatchVisibility,
                               bucket_shape, dense_shape)

RNG = np.random.default_rng(0)


# --------------------------------------------------------------------- vclock
ACTORS4 = ["a", "b", "c", "d"]
IDX4 = {a: i for i, a in enumerate(ACTORS4)}


def _sparse(dots):
    return Clock.zero().add_dots(Dot(ACTORS4[a], c) for a, c in dots)


class TestVClock:
    @given(st.lists(st.tuples(st.integers(0, 3), st.integers(1, 90)), max_size=40))
    @settings(max_examples=60, deadline=None)
    def test_dense_seen_matches_sparse(self, dots):
        sparse = _sparse(dots)
        dense = vclock.from_clock(sparse, IDX4, 4)
        probe_a = np.array([a for a, _ in dots] + [0, 1, 2, 3], np.int32)
        probe_c = np.array([c for _, c in dots] + [1, 64, 90, 128], np.int32)
        got = np.asarray(vclock.dots_seen(dense, jnp.asarray(probe_a), jnp.asarray(probe_c)))
        want = np.array([sparse.seen(Dot(ACTORS4[a], int(c)))
                         for a, c in zip(probe_a, probe_c)])
        assert (got == want).all()

    @given(st.lists(st.tuples(st.integers(0, 3), st.integers(1, 120)), max_size=40))
    @settings(max_examples=50, deadline=None)
    def test_roundtrip_sparse_dense_sparse(self, dots):
        sparse = _sparse(dots)
        dense = vclock.from_clock(sparse, IDX4, 4)
        assert vclock.to_clock(dense, ACTORS4) == sparse

    @given(st.lists(st.tuples(st.integers(0, 3), st.integers(1, 100)), max_size=30),
           st.lists(st.tuples(st.integers(0, 3), st.integers(1, 100)), max_size=30))
    @settings(max_examples=40, deadline=None)
    def test_dense_join_matches_sparse(self, d1, d2):
        s1, s2 = _sparse(d1), _sparse(d2)
        j = vclock.join(vclock.from_clock(s1, IDX4, 4),
                        vclock.from_clock(s2, IDX4, 4))
        assert vclock.to_clock(j, ACTORS4) == s1.join(s2)

    @given(st.lists(st.tuples(st.integers(0, 3), st.integers(1, 100)), max_size=30),
           st.lists(st.tuples(st.integers(0, 3), st.integers(1, 100)), max_size=30))
    @settings(max_examples=40, deadline=None)
    def test_dense_subtract_intersect_match_sparse(self, d1, d2):
        s1, s2 = _sparse(d1), _sparse(d2)
        a = vclock.from_clock(s1, IDX4, 4)
        b = vclock.from_clock(s2, IDX4, 4)
        assert vclock.to_clock(vclock.subtract(a, b), ACTORS4) == s1.subtract_clock(s2)
        assert vclock.to_clock(vclock.intersect(a, b), ACTORS4) == s1.intersect(s2)

    @given(st.lists(st.tuples(st.integers(0, 3), st.integers(1, 80)), max_size=25),
           st.lists(st.tuples(st.integers(0, 3), st.integers(1, 80)),
                    min_size=1, max_size=25))
    @settings(max_examples=40, deadline=None)
    def test_dense_add_dots_matches_sparse(self, base, extra):
        sparse = _sparse(base)
        dense = vclock.from_clock(sparse, IDX4, 4)
        added = vclock.add_dots(
            dense,
            jnp.asarray([a for a, _ in extra], jnp.int32),
            jnp.asarray([c for _, c in extra], jnp.int32))
        want = sparse.add_dots(Dot(ACTORS4[a], c) for a, c in extra)
        assert vclock.to_clock(added, ACTORS4) == want

    def test_subtract_is_origin_free(self):
        # Subtraction punches holes *below the base* — the old windowed
        # bitmap could not represent that without a scalar fallback.
        s1 = Clock.zero().add_dots(Dot("a", c) for c in range(1, 41))
        s2 = Clock.zero().add_dots(Dot("a", c) for c in (2, 9, 40))
        d = vclock.subtract(vclock.from_clock(s1, IDX4, 4),
                            vclock.from_clock(s2, IDX4, 4))
        assert vclock.to_clock(d, ACTORS4) == s1.subtract_clock(s2)
        assert int(vclock.popcount(d).sum()) == 37

    def test_no_window_cap(self):
        # A single run covers an arbitrarily wide span at constant cost.
        wide = Clock(base={"a": 1_000_000})
        dense = vclock.from_clock(wide, IDX4, 4)
        assert dense.n_runs == 1
        got = vclock.dots_seen(dense,
                               jnp.zeros(3, jnp.int32),
                               jnp.array([1, 999_999, 1_000_001], jnp.int32))
        assert np.asarray(got).tolist() == [True, True, False]


# ------------------------------------------------------------------- dot_seen
def _random_dense(n_actors, n_runs, hi, rng):
    """Random canonical interval arrays plus the sparse oracle."""
    names = [f"v{i}" for i in range(n_actors)]
    n_dots = n_actors * n_runs * 2
    sparse = Clock.zero().add_dots(
        Dot(names[int(a)], int(c))
        for a, c in zip(rng.integers(0, n_actors, n_dots),
                        rng.integers(1, hi, n_dots)))
    idx = {a: i for i, a in enumerate(names)}
    return vclock.from_clock(sparse, idx, n_actors), sparse, names


class TestDotSeenKernel:
    @pytest.mark.parametrize("n_actors,n_runs,n_dots,block_n", [
        (4, 8, 64, 32),
        (16, 32, 1000, 256),
        (128, 16, 4096, 1024),
        (3, 2, 17, 64),     # ragged: pad path
    ])
    def test_matches_ref(self, n_actors, n_runs, n_dots, block_n):
        dense, sparse, names = _random_dense(n_actors, n_runs, n_runs * 40, RNG)
        actors = jnp.asarray(RNG.integers(0, n_actors, n_dots), jnp.int32)
        counters = jnp.asarray(RNG.integers(1, n_runs * 40 + 80, n_dots), jnp.int32)
        got = dot_seen_pallas(dense.starts, dense.ends, actors, counters,
                              block_n=block_n, interpret=True)
        want = dot_seen_ref(dense.starts, dense.ends, actors, counters)
        assert (np.asarray(got) == np.asarray(want)).all()
        oracle = np.array([sparse.seen(Dot(names[int(a)], int(c)))
                           for a, c in zip(np.asarray(actors), np.asarray(counters))])
        assert (np.asarray(got) == oracle).all()

    def test_extremes(self):
        # Large counters stay exact through the f32 one-hot gather (< 2^24).
        starts = jnp.array([[1, 128], [1, 0]], jnp.int32)
        ends = jnp.array([[100, 128], [16_000_000, 0]], jnp.int32)
        actors = jnp.array([0, 0, 0, 1, 1], jnp.int32)
        counters = jnp.array([128, 127, 101, 16_000_000, 16_000_001], jnp.int32)
        got = dot_seen_pallas(starts, ends, actors, counters, block_n=32,
                              interpret=True)
        assert np.asarray(got).tolist() == [True, False, False, True, False]

    @pytest.mark.parametrize("n_actors,n_runs", [
        (1, 2000),   # one actor, R 2000 -> 2048: four run tiles, all in use
        (3, 1100),   # A 3 -> 8 padded rows, R 1100 -> 2048
        (5, 520),    # just past one tile: R 520 -> 1024
    ])
    def test_bucketed_matches_ref(self, n_actors, n_runs):
        # The widest row holds the odd counters 1, 3, ..., one run each, so
        # every probe's answer hangs on a single run column.
        names = [f"v{i}" for i in range(n_actors)]
        rng = np.random.default_rng(n_actors * 10_000 + n_runs)
        sparse = Clock.zero().add_dots(
            [Dot(names[0], 2 * k + 1) for k in range(n_runs)]
            + [Dot(names[int(a)], int(c)) for a, c in zip(
                rng.integers(1, max(n_actors, 2), 200),
                rng.integers(1, 4000, 200)) if a < n_actors])
        shape = dense_shape(sparse)
        assert shape == (n_actors, n_runs)
        a_pad, r_pad = bucket_shape(*shape)
        assert a_pad % ACTOR_BUCKET == 0 and r_pad % RUN_TILE == 0
        assert r_pad >= n_runs > r_pad // 2
        dense = vclock.from_clock(sparse, {a: i for i, a in enumerate(names)},
                                  a_pad, n_runs=r_pad)
        assert dense.starts.shape == (a_pad, r_pad)

        top = 2 * n_runs + 1   # the widest row's last run, then one past it
        probe_a = np.concatenate([np.zeros(4, np.int64),
                                  rng.integers(0, n_actors, 1500)])
        probe_c = np.concatenate([[top - 2, top - 1, top, 1],
                                  rng.integers(1, top + 2, 1500)])
        actors = jnp.asarray(probe_a, jnp.int32)
        counters = jnp.asarray(probe_c, jnp.int32)
        got = np.asarray(dot_seen_pallas(dense.starts, dense.ends, actors,
                                         counters, interpret=True))
        want = np.asarray(dot_seen_ref(dense.starts, dense.ends, actors,
                                       counters))
        oracle = np.array([sparse.seen(Dot(names[int(a)], int(c)))
                           for a, c in zip(probe_a, probe_c)])
        assert (got == want).all() and (got == oracle).all()
        assert got[:4].tolist() == [True, False, False, True]

    @pytest.mark.parametrize("col", [0, RUN_TILE - 1, RUN_TILE, 4 * RUN_TILE - 1])
    def test_single_run_found_in_its_tile_only(self, col):
        # One run in an otherwise empty (8, 4 tiles) clock: the tile holding
        # it must OR its hit into the output, and no other tile may clear it.
        starts = np.ones((ACTOR_BUCKET, 4 * RUN_TILE), np.int32)
        ends = np.zeros_like(starts)
        starts[2, col], ends[2, col] = 5_000, 5_002
        actors = jnp.array([2, 2, 2, 2, 2, 0, 7], jnp.int32)
        counters = jnp.array([4_999, 5_000, 5_001, 5_002, 5_003, 5_000, 5_000],
                             jnp.int32)
        got = dot_seen_pallas(jnp.asarray(starts), jnp.asarray(ends), actors,
                              counters, block_n=512, interpret=True)
        assert np.asarray(got).tolist() == [False, True, True, True, False,
                                            False, False]

    @pytest.mark.parametrize("counter,raises", [
        (MAX_COUNTER - 1, False),
        (MAX_COUNTER, False),
        (MAX_COUNTER + 1, True),
        (2**30, True),
    ])
    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_batch_visibility_counter_bound(self, counter, raises, use_pallas):
        # Counters travel through the kernel's f32 gather, exact below 2**24:
        # a larger one must raise where it is packed, never be misjudged.
        ts = Clock.zero().add_dots(
            [Dot("a", MAX_COUNTER), Dot("a", 7), Dot("b", 3)])
        vis = BatchVisibility(ts, use_pallas=use_pallas, interpret=True,
                              min_batch=1)
        dots = [Dot("a", counter), Dot("a", 7), Dot("b", 4)]
        if raises:
            with pytest.raises(ValueError, match="exceeds"):
                vis.seen_mask(dots)
        else:
            assert vis.seen_mask(dots).tolist() == [
                counter == MAX_COUNTER, True, False]


# ------------------------------------------------------------------ clock_ops
class TestClockOpsKernels:
    @pytest.mark.parametrize("n_actors,n_runs", [(4, 16), (8, 64), (13, 25)])
    def test_pallas_matches_ref_and_oracle(self, n_actors, n_runs):
        from repro.kernels.clock_ops import intersect, join, popcount, subtract

        rng = np.random.default_rng(n_actors * 100 + n_runs)
        da, sa, names = _random_dense(n_actors, n_runs, n_runs * 20, rng)
        db, sb, _ = _random_dense(n_actors, n_runs, n_runs * 20, rng)
        for op, sparse_want in [
            (join, sa.join(sb)),
            (subtract, sa.subtract_clock(sb)),
            (intersect, sa.intersect(sb)),
        ]:
            got_p = op(da, db, use_pallas=True, interpret=True)
            got_r = op(da, db, use_pallas=False)
            assert (np.asarray(got_p.starts) == np.asarray(got_r.starts)).all()
            assert (np.asarray(got_p.ends) == np.asarray(got_r.ends)).all()
            assert vclock.to_clock(got_p, names) == sparse_want

    def test_popcount(self):
        from repro.kernels.clock_ops import popcount

        dense, sparse, names = _random_dense(6, 12, 300, np.random.default_rng(7))
        got = np.asarray(popcount(dense, use_pallas=True, interpret=True))
        want = np.asarray(popcount(dense, use_pallas=False))
        assert (got == want).all()
        assert int(got.sum()) == sparse.n_events()


# ------------------------------------------------------------ flash attention
class TestFlashAttention:
    @pytest.mark.parametrize("B,Hq,Hkv,T,D,dtype", [
        (1, 2, 2, 128, 64, jnp.float32),
        (2, 4, 2, 256, 64, jnp.float32),   # GQA group 2
        (1, 8, 1, 128, 128, jnp.float32),  # MQA-ish
        (1, 2, 2, 256, 128, jnp.bfloat16),
    ])
    def test_causal_matches_ref(self, B, Hq, Hkv, T, D, dtype):
        q = jnp.asarray(RNG.standard_normal((B, Hq, T, D)), dtype)
        k = jnp.asarray(RNG.standard_normal((B, Hkv, T, D)), dtype)
        v = jnp.asarray(RNG.standard_normal((B, Hkv, T, D)), dtype)
        got = flash_attention_pallas(q, k, v, causal=True, block_q=64, block_kv=64)
        want = attention_ref(q, k, v, causal=True)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                        atol=tol, rtol=tol)

    @pytest.mark.parametrize("window", [64, 128, 999])
    def test_sliding_window(self, window):
        B, H, T, D = 1, 2, 256, 64
        q = jnp.asarray(RNG.standard_normal((B, H, T, D)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((B, H, T, D)), jnp.float32)
        v = jnp.asarray(RNG.standard_normal((B, H, T, D)), jnp.float32)
        got = flash_attention_pallas(q, k, v, causal=True, window=window,
                                     block_q=64, block_kv=64)
        want = attention_ref(q, k, v, causal=True, window=window)
        assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)

    def test_noncausal(self):
        B, H, T, D = 1, 1, 128, 64
        q = jnp.asarray(RNG.standard_normal((B, H, T, D)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((B, H, T, D)), jnp.float32)
        v = jnp.asarray(RNG.standard_normal((B, H, T, D)), jnp.float32)
        got = flash_attention_pallas(q, k, v, causal=False, block_q=64, block_kv=64)
        want = attention_ref(q, k, v, causal=False)
        assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------ decode attention
class TestDecodeAttention:
    @pytest.mark.parametrize("B,Hq,Hkv,S,D,dtype", [
        (2, 4, 4, 256, 64, jnp.float32),
        (1, 8, 2, 512, 64, jnp.float32),   # GQA group 4
        (2, 4, 1, 256, 128, jnp.bfloat16),
    ])
    def test_matches_ref(self, B, Hq, Hkv, S, D, dtype):
        q = jnp.asarray(RNG.standard_normal((B, Hq, D)), dtype)
        k = jnp.asarray(RNG.standard_normal((B, Hkv, S, D)), dtype)
        v = jnp.asarray(RNG.standard_normal((B, Hkv, S, D)), dtype)
        lens = jnp.asarray(RNG.integers(1, S + 1, B), jnp.int32)
        got = decode_attention_pallas(q, k, v, lens, block_kv=128)
        want = decode_attention_ref(q, k, v, lens)
        tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
        assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                        atol=tol, rtol=tol)

    def test_windowed_decode(self):
        B, Hq, Hkv, S, D = 1, 4, 2, 512, 64
        q = jnp.asarray(RNG.standard_normal((B, Hq, D)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((B, Hkv, S, D)), jnp.float32)
        v = jnp.asarray(RNG.standard_normal((B, Hkv, S, D)), jnp.float32)
        lens = jnp.array([400], jnp.int32)
        got = decode_attention_pallas(q, k, v, lens, window=128, block_kv=128)
        want = decode_attention_ref(q, k, v, lens, window=128)
        assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------- mamba scan
class TestMambaScan:
    @pytest.mark.parametrize("B,T,Dm,N,chunk,block_d", [
        (1, 64, 32, 8, 32, 32),
        (2, 128, 64, 16, 64, 32),
        (1, 96, 48, 16, 32, 16),
    ])
    def test_matches_ref(self, B, T, Dm, N, chunk, block_d):
        x = jnp.asarray(RNG.standard_normal((B, T, Dm)), jnp.float32)
        delta = jnp.asarray(np.abs(RNG.standard_normal((B, T, Dm))) * 0.1, jnp.float32)
        A = -jnp.asarray(np.abs(RNG.standard_normal((Dm, N))) + 0.1, jnp.float32)
        Bm = jnp.asarray(RNG.standard_normal((B, T, N)), jnp.float32)
        Cm = jnp.asarray(RNG.standard_normal((B, T, N)), jnp.float32)
        Dp = jnp.asarray(RNG.standard_normal(Dm), jnp.float32)
        got = mamba_scan_pallas(x, delta, A, Bm, Cm, Dp, chunk=chunk, block_d=block_d)
        want, _ = mamba_scan_ref(x, delta, A, Bm, Cm, Dp)
        assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)

    def test_step_continues_scan(self):
        """Decode step after a prefill scan equals one longer scan."""
        B, T, Dm, N = 1, 32, 16, 8
        x = jnp.asarray(RNG.standard_normal((B, T + 1, Dm)), jnp.float32)
        delta = jnp.asarray(np.abs(RNG.standard_normal((B, T + 1, Dm))) * 0.1, jnp.float32)
        A = -jnp.asarray(np.abs(RNG.standard_normal((Dm, N))) + 0.1, jnp.float32)
        Bm = jnp.asarray(RNG.standard_normal((B, T + 1, N)), jnp.float32)
        Cm = jnp.asarray(RNG.standard_normal((B, T + 1, N)), jnp.float32)
        Dp = jnp.asarray(RNG.standard_normal(Dm), jnp.float32)
        y_full, _ = mamba_scan_ref(x, delta, A, Bm, Cm, Dp)
        y_pre, h = mamba_scan_ref(x[:, :T], delta[:, :T], A, Bm[:, :T], Cm[:, :T], Dp)
        y_step, _ = mamba_step_ref(x[:, T], delta[:, T], A, Bm[:, T], Cm[:, T], Dp, h)
        assert_allclose(np.asarray(y_step), np.asarray(y_full[:, T]), atol=1e-5, rtol=1e-5)
