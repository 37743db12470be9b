"""Pallas kernel correctness: sweeps of shapes/dtypes vs pure-jnp oracles.

Kernels execute in interpret=True mode on CPU (the kernel body runs in
Python with the same tiling/grid semantics as on TPU).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import UOTConfig, sinkhorn_uot_fused, sinkhorn_uot_uv
from repro.kernels import ops, ref
from repro.kernels.uot_fused import fused_iteration, colsum
from repro.kernels.uot_halfpass import (
    scale_rows_accum_cols, scale_cols_accum_rows)
from repro.kernels.uot_uv_fused import uv_iteration, materialize_coupling
from repro.kernels.vmem import VMEM_LIMIT_BYTES


def rand(shape, seed=0, dtype=jnp.float32, lo=0.1, hi=2.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(lo, hi, size=shape), dtype=dtype)


TOL = {jnp.float32: dict(rtol=2e-6, atol=1e-8),
       jnp.bfloat16: dict(rtol=2e-2, atol=1e-3)}


class TestFusedIterationKernel:
    @pytest.mark.parametrize("M,N,bm", [
        (8, 128, 8), (64, 128, 8), (64, 256, 16), (256, 384, 64),
        (512, 128, 256), (128, 1024, 32),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, M, N, bm, dtype):
        A = rand((M, N), seed=M + N, dtype=dtype)
        fcol = rand((N,), seed=1)
        a = rand((M,), seed=2)
        fi = 0.9
        out, cs = fused_iteration(A, fcol, a, fi=fi, block_m=bm, interpret=True)
        out_r, cs_r = ref.fused_iteration_ref(A, fcol, a, fi=fi)
        tol = TOL[dtype]
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(out_r.astype(dtype), np.float32), **tol)
        np.testing.assert_allclose(cs, cs_r, rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5)

    @pytest.mark.parametrize("fi", [1.0, 0.5, 0.909])
    def test_fi_variants(self, fi):
        A = rand((64, 256))
        fcol, a = rand((256,), 1), rand((64,), 2)
        out, cs = fused_iteration(A, fcol, a, fi=fi, block_m=16, interpret=True)
        out_r, cs_r = ref.fused_iteration_ref(A, fcol, a, fi=fi)
        np.testing.assert_allclose(out, out_r, rtol=2e-6)
        np.testing.assert_allclose(cs, cs_r, rtol=2e-6)

    def test_zero_rows_are_noop(self):
        """Zero padding invariance: padded rows/cols stay zero, sums exact."""
        A = rand((32, 128))
        A = A.at[16:, :].set(0.0)
        fcol, a = rand((128,), 1), rand((32,), 2).at[16:].set(0.0)
        out, cs = fused_iteration(A, fcol, a, fi=0.9, block_m=8, interpret=True)
        assert float(jnp.abs(out[16:, :]).max()) == 0.0

    def test_colsum_kernel(self):
        A = rand((96, 256))
        np.testing.assert_allclose(
            colsum(A, block_m=32, interpret=True), ref.colsum_ref(A), rtol=1e-6)


class TestHalfpassKernels:
    @pytest.mark.parametrize("M,N,bm,bn", [
        (64, 256, 16, 128), (128, 512, 32, 256), (256, 1024, 64, 512),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_scale_rows(self, M, N, bm, bn, dtype):
        A = rand((M, N), dtype=dtype)
        frow = rand((M,), 3)
        out, cs = scale_rows_accum_cols(A, frow, block_m=bm, block_n=bn,
                                        interpret=True)
        out_r, cs_r = ref.scale_rows_accum_cols_ref(A, frow)
        tol = TOL[dtype]
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(out_r.astype(dtype), np.float32), **tol)
        np.testing.assert_allclose(cs, cs_r, rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5)

    @pytest.mark.parametrize("M,N,bm,bn", [
        (64, 256, 16, 128), (128, 512, 32, 256),
    ])
    def test_scale_cols(self, M, N, bm, bn):
        A = rand((M, N))
        fcol = rand((N,), 4)
        out, rs = scale_cols_accum_rows(A, fcol, block_m=bm, block_n=bn,
                                        interpret=True)
        out_r, rs_r = ref.scale_cols_accum_rows_ref(A, fcol)
        np.testing.assert_allclose(out, out_r, rtol=2e-6)
        np.testing.assert_allclose(rs, rs_r, rtol=2e-6)


class TestUVKernel:
    @pytest.mark.parametrize("M,N,bm", [(64, 128, 8), (128, 384, 32),
                                        (256, 1024, 64)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_uv_iteration(self, M, N, bm, dtype):
        K = rand((M, N), dtype=dtype)
        v = rand((N,), 5)
        a = rand((M,), 6)
        u, ktu = uv_iteration(K, v, a, fi=0.9, block_m=bm, interpret=True)
        u_r, ktu_r = ref.uv_iteration_ref(K, v, a, fi=0.9)
        rtol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(u, u_r, rtol=rtol)
        np.testing.assert_allclose(ktu, ktu_r, rtol=rtol)

    def test_materialize(self):
        K = rand((64, 256))
        u, v = rand((64,), 7), rand((256,), 8)
        P = materialize_coupling(K, u, v, block_m=16, interpret=True)
        np.testing.assert_allclose(P, ref.materialize_coupling_ref(K, u, v),
                                   rtol=2e-6)


class TestAssembledSolvers:
    """Kernel-built solvers must match the core jnp solvers end to end."""

    def make_problem(self, M=100, N=77, reg=0.1, seed=0):
        rng = np.random.default_rng(seed)
        C = rng.uniform(0, 1, size=(M, N)).astype(np.float32)
        a = rng.uniform(0.5, 1.5, size=M).astype(np.float32)
        b = rng.uniform(0.5, 1.5, size=N).astype(np.float32)
        a, b = a / a.sum(), b / b.sum() * 1.2
        K = np.exp(-C / reg) * (a[:, None] * b[None, :])
        return jnp.asarray(K), jnp.asarray(a), jnp.asarray(b)

    def test_solve_fused_matches_core(self):
        K, a, b = self.make_problem()
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=40)
        A_core, _ = sinkhorn_uot_fused(K, a, b, cfg)
        A_kern, _ = ops.solve_fused(K, a, b, cfg, block_m=16, interpret=True)
        np.testing.assert_allclose(A_kern, A_core, rtol=3e-5, atol=1e-8)

    def test_solve_halfpass_matches_core(self):
        K, a, b = self.make_problem()
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=40)
        A_core, _ = sinkhorn_uot_fused(K, a, b, cfg)
        A_kern, _ = ops.solve_halfpass(K, a, b, cfg, block_m=16, block_n=128,
                                       interpret=True)
        np.testing.assert_allclose(A_kern, A_core, rtol=3e-5, atol=1e-8)

    def test_solve_uv_matches_core(self):
        K, a, b = self.make_problem()
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=60)
        P_core, (u_c, v_c), _ = sinkhorn_uot_uv(K, a, b, cfg)
        P_kern, (u_k, v_k) = ops.solve_uv(K, a, b, cfg, block_m=16,
                                          interpret=True)
        np.testing.assert_allclose(v_k, v_c, rtol=3e-5)
        np.testing.assert_allclose(P_kern, P_core, rtol=3e-4, atol=1e-8)

    def test_block_autotune_bounds(self):
        assert ops.pick_block_m(10_000, 512) == 512
        assert ops.streamed_vmem_bytes(512, 512) <= VMEM_LIMIT_BYTES
        # the paper's largest size: the block shrinks until it fits
        bm = ops.pick_block_m(20480, 20480)
        assert bm >= 8 and ops.streamed_vmem_bytes(
            bm, 20480) <= VMEM_LIMIT_BYTES < ops.streamed_vmem_bytes(
                2 * bm, 20480)
        # a row too wide for any block gets the sublane floor
        assert ops.pick_block_m(100_000, 1_000_000) == 8
