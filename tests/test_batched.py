"""Batched + mixed-precision solving path: kernels, wrappers, serving.

Pallas kernels run with ``impl='kernel', interpret=True`` so the real
(batch, row_blocks) grid schedule executes on CPU CI; the vectorized XLA
path (``impl='jnp'``, the non-TPU default) is held to the same parity bars.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (UOTConfig, sinkhorn_uot_fused,
                        sinkhorn_uot_fused_batched)
from repro.kernels import ops, ref
from repro.kernels.uot_batched import (
    batched_colsum, batched_fused_iteration, batched_fused_iteration_frow,
    batched_materialize_coupling, batched_uv_iteration)
from repro.kernels.uot_fused import fused_iteration
from repro.serve import UOTBatchEngine


def rand(shape, seed=0, dtype=jnp.float32, lo=0.1, hi=2.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(lo, hi, size=shape), dtype=dtype)


def make_stack(B, M, N, reg=0.1, seed=0):
    rng = np.random.default_rng(seed)
    C = rng.uniform(0, 1, size=(B, M, N)).astype(np.float32)
    a = rng.uniform(0.5, 1.5, size=(B, M)).astype(np.float32)
    b = rng.uniform(0.5, 1.5, size=(B, N)).astype(np.float32)
    a = a / a.sum(axis=1, keepdims=True)
    b = b / b.sum(axis=1, keepdims=True) * 1.2
    K = np.exp(-C / reg) * (a[:, :, None] * b[:, None, :])
    return jnp.asarray(K), jnp.asarray(a), jnp.asarray(b)


class TestBatchedKernels:
    @pytest.mark.parametrize("B,M,N,bm", [
        (1, 8, 128, 8), (3, 32, 128, 8), (4, 64, 256, 16), (2, 128, 384, 64),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_fused_iteration_matches_ref(self, B, M, N, bm, dtype):
        A = rand((B, M, N), seed=B + M + N, dtype=dtype)
        fcol = rand((B, N), seed=1)
        a = rand((B, M), seed=2)
        out, cs = batched_fused_iteration(A, fcol, a, fi=0.9, block_m=bm,
                                          interpret=True)
        out_r, cs_r = ref.batched_fused_iteration_ref(A, fcol, a, fi=0.9)
        if dtype == jnp.bfloat16:
            tol = dict(rtol=2e-2, atol=1e-3)
        else:
            tol = dict(rtol=2e-6, atol=1e-8)
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(out_r.astype(dtype), np.float32), **tol)
        np.testing.assert_allclose(
            cs, cs_r, rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5)

    def test_matches_single_problem_kernel_per_slice(self):
        """The batched grid must reproduce the single-problem kernel exactly
        (same block schedule per problem -> same accumulation order)."""
        B, M, N, bm = 3, 64, 256, 16
        A, fcol, a = rand((B, M, N)), rand((B, N), 1), rand((B, M), 2)
        out, cs = batched_fused_iteration(A, fcol, a, fi=0.9, block_m=bm,
                                          interpret=True)
        for i in range(B):
            out_i, cs_i = fused_iteration(A[i], fcol[i], a[i], fi=0.9,
                                          block_m=bm, interpret=True)
            np.testing.assert_array_equal(np.asarray(out[i]),
                                          np.asarray(out_i))
            np.testing.assert_array_equal(np.asarray(cs[i]), np.asarray(cs_i))

    @pytest.mark.parametrize("kernel", ["fused", "batched", "frow"])
    def test_in_place_kernel_leaves_the_callers_array(self, kernel):
        """The iteration kernels write A' over A's buffer. A caller that
        keeps its A still sees it unchanged, and the outputs match the
        oracle (a frozen lane of the masked kernel gives back its input)."""
        B, M, N, bm = 2, 64, 256, 16
        A, fcol, a = rand((B, M, N)), rand((B, N), 1), rand((B, M), 2)
        out_r, cs_r = ref.batched_fused_iteration_ref(A, fcol, a, fi=0.9)
        if kernel == "fused":
            A, fcol, a, out_r, cs_r = A[0], fcol[0], a[0], out_r[0], cs_r[0]
        before = np.array(A)
        if kernel == "fused":
            out, cs = fused_iteration(A, fcol, a, fi=0.9, block_m=bm,
                                      interpret=True)
        elif kernel == "batched":
            out, cs = batched_fused_iteration(A, fcol, a, fi=0.9,
                                              block_m=bm, interpret=True)
        else:
            out, cs, _ = batched_fused_iteration_frow(
                A, fcol, a, jnp.array([1.0, 0.0]), fi=0.9, block_m=bm,
                interpret=True)
            out_r = out_r.at[1].set(A[1])
            cs_r = cs_r.at[1].set(A[1].sum(axis=0))
        np.testing.assert_array_equal(np.asarray(A), before)
        np.testing.assert_allclose(out, out_r, rtol=2e-6, atol=1e-8)
        np.testing.assert_allclose(cs, cs_r, rtol=1e-5)

    def test_colsum(self):
        A = rand((3, 96, 256))
        np.testing.assert_allclose(
            batched_colsum(A, block_m=32, interpret=True),
            ref.batched_colsum_ref(A), rtol=1e-6)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_uv_iteration(self, dtype):
        B, M, N = 2, 64, 128
        K = rand((B, M, N), dtype=dtype)
        v, a = rand((B, N), 5), rand((B, M), 6)
        u, ktu = batched_uv_iteration(K, v, a, fi=0.9, block_m=16,
                                      interpret=True)
        u_r, ktu_r = ref.batched_uv_iteration_ref(K, v, a, fi=0.9)
        rtol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(u, u_r, rtol=rtol)
        np.testing.assert_allclose(ktu, ktu_r, rtol=rtol)

    def test_materialize(self):
        B, M, N = 2, 64, 128
        K = rand((B, M, N))
        u, v = rand((B, M), 7), rand((B, N), 8)
        P = batched_materialize_coupling(K, u, v, block_m=16, interpret=True)
        np.testing.assert_allclose(P, ref.batched_materialize_coupling_ref(
            K, u, v), rtol=2e-6)


class TestSolveFusedBatched:
    CFG = UOTConfig(reg=0.1, reg_m=1.0, num_iters=25)

    @pytest.mark.parametrize("impl", ["kernel", "jnp"])
    def test_matches_per_sample_loop(self, impl):
        """ISSUE-1 acceptance: batched == loop of solve_fused to 1e-5."""
        K, a, b = make_stack(4, 48, 130)
        P, cs = ops.solve_fused_batched(K, a, b, self.CFG, block_m=16,
                                        interpret=True, impl=impl)
        for i in range(4):
            P_i, cs_i = ops.solve_fused(K[i], a[i], b[i], self.CFG,
                                        block_m=16, interpret=True)
            np.testing.assert_allclose(P[i], P_i, rtol=1e-5, atol=1e-8)
            np.testing.assert_allclose(cs[i], cs_i, rtol=1e-5)

    def test_matches_vmap_semantic_reference(self):
        K, a, b = make_stack(3, 40, 96)
        P, _ = ops.solve_fused_batched(K, a, b, self.CFG, block_m=8,
                                       interpret=True, impl="kernel")
        P_ref, _ = sinkhorn_uot_fused_batched(K, a, b, self.CFG)
        np.testing.assert_allclose(P, P_ref, rtol=3e-5, atol=1e-8)

    @pytest.mark.parametrize("impl", ["kernel", "jnp"])
    def test_bf16_storage_tolerance(self, impl):
        """bf16 storage / fp32 accumulation stays within bf16 rounding of
        the fp32 solve (relative error ~2^-8 per stored value)."""
        K, a, b = make_stack(3, 64, 128, seed=1)
        P32, _ = ops.solve_fused_batched(K, a, b, self.CFG, block_m=16,
                                         interpret=True, impl=impl)
        Pbf, _ = ops.solve_fused_batched(K, a, b, self.CFG, block_m=16,
                                         interpret=True, impl=impl,
                                         storage_dtype=jnp.bfloat16)
        assert Pbf.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(Pbf, np.float32),
                                   np.asarray(P32), rtol=5e-2, atol=1e-4)
        # mass must be preserved to bf16 tolerance too, not just pointwise
        np.testing.assert_allclose(
            np.asarray(Pbf, np.float32).sum(), np.asarray(P32).sum(),
            rtol=1e-2)

    def test_bf16_via_cfg_dtype(self):
        """UOTConfig(dtype=bf16) selects the storage mode without a kwarg."""
        K, a, b = make_stack(2, 32, 128)
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=10,
                        dtype=jnp.bfloat16)
        P, _ = ops.solve_fused_batched(K, a, b, cfg, block_m=16,
                                       interpret=True)
        assert P.dtype == jnp.bfloat16

    def test_solve_uv_batched_matches_per_sample(self):
        K, a, b = make_stack(3, 48, 96)
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=30)
        for impl in ["kernel", "jnp"]:
            P, (u, v) = ops.solve_uv_batched(K, a, b, cfg, block_m=16,
                                             interpret=True, impl=impl)
            for i in range(3):
                P_i, (u_i, v_i) = ops.solve_uv(K[i], a[i], b[i], cfg,
                                               block_m=16, interpret=True)
                np.testing.assert_allclose(P[i], P_i, rtol=1e-5, atol=1e-8)
                np.testing.assert_allclose(u[i], u_i, rtol=1e-5)
                np.testing.assert_allclose(v[i], v_i, rtol=1e-5)


class TestRaggedBucketing:
    CFG = UOTConfig(reg=0.1, reg_m=1.0, num_iters=20)

    def test_bucket_problems_groups_by_padded_shape(self):
        shapes = [(20, 100), (60, 128), (17, 90), (65, 128), (64, 128)]
        buckets = ops.bucket_problems(shapes, m_bucket=64, n_bucket=128)
        assert buckets[(64, 128)] == [0, 1, 2, 4]
        assert buckets[(128, 128)] == [3]

    def test_ragged_solve_matches_standalone(self):
        """Padding a problem up to its bucket shape must not change its
        answer (zero rows/cols carry no mass, factors stay 1)."""
        rng = np.random.default_rng(3)
        problems = []
        for (m, n) in [(20, 100), (32, 128), (17, 100), (64, 200), (20, 100)]:
            problems.append((
                jnp.asarray(rng.uniform(0.1, 2, (m, n)), jnp.float32),
                jnp.asarray(rng.uniform(0.1, 2, (m,)), jnp.float32),
                jnp.asarray(rng.uniform(0.1, 2, (n,)), jnp.float32)))
        results = ops.solve_fused_bucketed(problems, self.CFG,
                                           interpret=True, max_batch=2)
        for (A0, a, b), (P, cs) in zip(problems, results):
            assert P.shape == A0.shape
            P_i, cs_i = ops.solve_fused(A0, a, b, self.CFG, interpret=True)
            np.testing.assert_allclose(P, P_i, rtol=1e-5, atol=1e-8)
            np.testing.assert_allclose(cs, cs_i, rtol=1e-5)


class TestUOTBatchEngine:
    def test_submit_flush_parity(self):
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=20)
        engine = UOTBatchEngine(cfg, max_batch=3, interpret=True)
        rng = np.random.default_rng(7)
        probs = {}
        for (m, n) in [(24, 100), (60, 120), (24, 100), (100, 250)]:
            K = rng.uniform(0.1, 2, (m, n)).astype(np.float32)
            a = rng.uniform(0.1, 2, m).astype(np.float32)
            b = rng.uniform(0.1, 2, n).astype(np.float32)
            rid = engine.submit(K, a, b)
            probs[rid] = (K, a, b)
        assert engine.pending == 4
        out = engine.flush()
        assert engine.pending == 0
        assert set(out) == set(probs)
        for rid, (K, a, b) in probs.items():
            P_i, _ = ops.solve_fused(jnp.asarray(K), jnp.asarray(a),
                                     jnp.asarray(b), cfg, interpret=True)
            np.testing.assert_allclose(out[rid], P_i, rtol=1e-5, atol=1e-8)

    def test_flush_empty(self):
        engine = UOTBatchEngine(UOTConfig(num_iters=5), interpret=True)
        assert engine.flush() == {}

    def test_repeat_flushes_reuse_compiled_solves(self):
        """Flushes whose bucket shapes repeat must hit the jit cache, even
        when queue depths jitter (batch is canonicalized to powers of 2)."""
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=5)
        engine = UOTBatchEngine(cfg, max_batch=8, interpret=True,
                                impl="jnp")
        rng = np.random.default_rng(11)

        def enqueue(n, mn):
            for _ in range(n):
                m, n_ = mn
                engine.submit(rng.uniform(0.1, 2, (m, n_)).astype(np.float32),
                              rng.uniform(0.1, 2, m).astype(np.float32),
                              rng.uniform(0.1, 2, n_).astype(np.float32))

        ops.reset_bucketed_cache_stats()
        enqueue(3, (20, 100))
        engine.flush()
        s1 = engine.cache_stats()
        assert s1 == {"hits": 0, "misses": 1}
        # _cache_size is a private jax API; use it when present for a
        # stronger no-recompile assertion, but don't depend on it
        sizer = getattr(ops.solve_fused_batched, "_cache_size", None)
        jit_entries = sizer() if sizer else None

        # same bucket, different queue depth within the same pow2 chunk
        enqueue(4, (24, 90))
        engine.flush()
        s2 = engine.cache_stats()
        assert s2 == {"hits": 1, "misses": 1}
        if sizer:
            assert sizer() == jit_entries, \
                "repeat flush recompiled the bucket solve"

        # a genuinely new chunk size is a miss exactly once
        enqueue(7, (20, 100))
        engine.flush()
        assert engine.cache_stats() == {"hits": 1, "misses": 2}
        enqueue(6, (20, 100))
        engine.flush()
        assert engine.cache_stats() == {"hits": 2, "misses": 2}

    def test_canonical_batch(self):
        assert [ops.canonical_batch(n, 8) for n in (1, 2, 3, 5, 8)] == \
            [1, 2, 4, 8, 8]
        assert ops.canonical_batch(33, 48) == 48


class TestPerLaneEarlyExit:
    """cfg.tol on the batched path: converged lanes freeze, loop ends when
    every lane (not each lane's worst-case budget) is done."""

    def _stack(self):
        # peaky cost (slow) + flat cost (fast) in one stack
        from benchmarks.common import make_problem
        probs = [make_problem(32, 128, reg=0.1, seed=5 + i, peak=peak)
                 for i, peak in enumerate((1.0, 6.0))]
        return tuple(jnp.stack(xs) for xs in zip(*probs))

    @pytest.mark.parametrize("impl", ["jnp", "kernel"])
    def test_each_lane_matches_its_single_problem_tol_solve(self, impl):
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=300, tol=1e-4)
        K, a, b = self._stack()
        P, cs = ops.solve_fused_batched(K, a, b, cfg, block_m=16,
                                        interpret=True, impl=impl)
        iter_counts = []
        for i in range(2):
            A_core, stats = sinkhorn_uot_fused(K[i], a[i], b[i], cfg)
            iter_counts.append(int(stats["iters"]))
            np.testing.assert_allclose(P[i], A_core, rtol=3e-5, atol=1e-8)
        assert iter_counts[0] < iter_counts[1], \
            "test needs heterogeneous convergence to mean anything"

    @pytest.mark.parametrize("num_iters", [0, 1, 2, 300])
    def test_kernel_stops_each_lane_where_jnp_does(self, num_iters):
        """The kernel path runs the tol loop's first pass before the loop,
        into a new buffer. Its couplings and column sums match the jnp
        path's, and each lane's coupling is the fixed-iteration solve at
        the iteration where that lane stopped, not one before."""
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=num_iters, tol=1e-4)
        K, a, b = self._stack()
        P, cs = ops.solve_fused_batched(K, a, b, cfg, block_m=16,
                                        interpret=True, impl="kernel")
        P_jnp, cs_jnp = ops.solve_fused_batched(K, a, b, cfg, impl="jnp")
        np.testing.assert_allclose(P, P_jnp, rtol=1e-5, atol=1e-8)
        np.testing.assert_allclose(cs, cs_jnp, rtol=1e-5)

        def fixed(i, n):
            return ops.solve_fused_batched(
                K, a, b, dataclasses.replace(cfg, tol=None, num_iters=n),
                impl="jnp")[0][i]

        stops = []
        for i in range(2):
            stop = int(sinkhorn_uot_fused(K[i], a[i], b[i], cfg)[1]["iters"])
            np.testing.assert_allclose(P[i], fixed(i, stop), rtol=1e-5,
                                       atol=1e-8)
            if stop:
                gap = np.abs(P[i] - fixed(i, stop)).max()
                assert np.abs(P[i] - fixed(i, stop - 1)).max() > 10 * gap
            stops.append(stop)
        if num_iters > 2:
            assert stops[0] < stops[1], \
                "test needs heterogeneous convergence to mean anything"

    def test_matches_stepped_lane_pool(self):
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=300, tol=1e-4)
        K, a, b = self._stack()
        P, _ = ops.solve_fused_batched(K, a, b, cfg, impl="jnp")
        st = ops.make_lane_state(2, 32, 128, cfg)
        for i in range(2):
            st = ops.lane_admit(st, jnp.int32(i), K[i], a[i], b[i])
        for _ in range(100):
            st = ops.solve_fused_stepped(st, 6, cfg, impl="jnp")
            if bool(np.asarray(ops.lane_done(st, cfg.num_iters)).all()):
                break
        np.testing.assert_allclose(st.P, P, rtol=1e-6, atol=1e-9)


class TestJnpBatchedReference:
    def test_vmap_reference_matches_loop(self):
        K, a, b = make_stack(3, 30, 70)
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=15)
        P, stats = sinkhorn_uot_fused_batched(K, a, b, cfg)
        assert P.shape == K.shape
        assert stats["iters"].shape == (3,)
        for i in range(3):
            P_i, _ = sinkhorn_uot_fused(K[i], a[i], b[i], cfg)
            np.testing.assert_allclose(P[i], P_i, rtol=1e-6, atol=1e-9)


class TestBlockPicker:
    def test_mixed_itemsize_earns_larger_blocks(self):
        # same N: bf16 storage fits at least the fp32 block, usually larger
        assert ops.pick_block_m(4096, 65536, 2) >= ops.pick_block_m(
            4096, 65536, 4)

    def test_clamps_to_problem_height(self):
        assert ops.pick_block_m(256, 256) <= 256
        assert ops.pick_block_m(8, 128) == 8

    def test_bf16_sublane_floor(self):
        assert ops.pick_block_m(8, 10_000_000, 2) == 16
        assert ops.sublane_for(jnp.bfloat16) == 16
        assert ops.sublane_for(jnp.float32) == 8
