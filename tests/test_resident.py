"""VMEM-resident solver tier: parity, convergence semantics, dispatch.

The resident kernels run whole solves (or whole scheduler chunks) with each
lane's tile on-chip, so the contract is: same iterate, same per-lane
iteration count as the streamed tier — exactly for fp32, and for bf16
storage the resident trajectory is the fp32 trajectory rounded ONCE (the
streamed path's per-iteration rounding disappears by design, so bf16 parity
is held against the fp32 reference, not bit-against-streamed). Kernels run
with ``impl='kernel', interpret=True`` so the real lane-grid schedule
executes on CPU CI; the jnp mirror is held to the same bars.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import UOTConfig, sinkhorn_uot_fused
from repro.kernels import ops

IMPLS = ["jnp", "kernel"]


def make_stack(B, M, N, reg=0.1, seed=0, peak_spread=True):
    """Random problem stack; with ``peak_spread`` the per-problem cost
    scale varies so tol-based runs converge at different iteration counts
    (the interesting case for per-lane early exit)."""
    rng = np.random.default_rng(seed)
    peaks = rng.uniform(1.0, 6.0, B) if peak_spread else np.ones(B)
    C = rng.uniform(0, 1, size=(B, M, N)).astype(np.float32)
    C *= peaks[:, None, None]
    a = rng.uniform(0.5, 1.5, size=(B, M)).astype(np.float32)
    b = rng.uniform(0.5, 1.5, size=(B, N)).astype(np.float32)
    a = a / a.sum(axis=1, keepdims=True)
    b = b / b.sum(axis=1, keepdims=True) * 1.3
    K = np.exp(-C / reg) * (a[:, :, None] * b[:, None, :])
    return jnp.asarray(K), jnp.asarray(a), jnp.asarray(b)


def _resident(K, a, b, cfg, impl, **kw):
    interpret = True if impl == "kernel" else None
    return ops.solve_fused_resident(K, a, b, cfg, impl=impl,
                                    interpret=interpret, **kw)


class TestResidentOneShot:
    """One-shot resident solves vs the core streamed reference."""

    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("tol", [None, 1e-3])
    def test_fp32_matches_core_iterates_and_counts(self, impl, tol):
        B, M, N = 3, 40, 200
        K, a, b = make_stack(B, M, N, seed=1)
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=25, tol=tol)
        P, colsum, iters, err = _resident(K, a, b, cfg, impl)
        for i in range(B):
            P_ref, stats = sinkhorn_uot_fused(K[i], a[i], b[i], cfg)
            np.testing.assert_allclose(np.asarray(P[i]), np.asarray(P_ref),
                                       rtol=2e-6, atol=1e-9)
            assert int(iters[i]) == int(stats["iters"])
            np.testing.assert_allclose(np.asarray(colsum[i]),
                                       np.asarray(P_ref).sum(0),
                                       rtol=1e-5, atol=1e-9)
        if tol is not None:
            # the peak spread must actually exercise heterogeneous counts
            assert len(set(np.asarray(iters).tolist())) > 1
            assert (np.asarray(err) <= tol).all()

    @pytest.mark.parametrize("tol", [None, 1e-3])
    def test_kernel_matches_jnp_mirror(self, tol):
        B, M, N = 4, 24, 130
        K, a, b = make_stack(B, M, N, seed=2)
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=30, tol=tol)
        Pk, csk, itk, errk = _resident(K, a, b, cfg, "kernel")
        Pj, csj, itj, errj = _resident(K, a, b, cfg, "jnp")
        np.testing.assert_allclose(np.asarray(Pk), np.asarray(Pj),
                                   rtol=1e-6, atol=1e-9)
        np.testing.assert_array_equal(np.asarray(itk), np.asarray(itj))
        np.testing.assert_allclose(np.asarray(csk), np.asarray(csj),
                                   rtol=1e-6, atol=1e-9)

    @pytest.mark.parametrize("impl", IMPLS)
    def test_bf16_storage_rounds_once(self, impl):
        """Resident bf16 = fp32 trajectory downcast at the end: it must
        match the fp32 core solve to one-rounding tolerance AND be at
        least as close to it as the streamed bf16 path, whose per-iteration
        rounding accumulates."""
        B, M, N = 3, 32, 140
        K, a, b = make_stack(B, M, N, seed=3)
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=25,
                        dtype=jnp.bfloat16)
        cfg32 = UOTConfig(reg=0.1, reg_m=1.0, num_iters=25)
        P, _, iters, _ = _resident(K, a, b, cfg, impl)
        assert P.dtype == jnp.bfloat16
        P_stream, _ = ops.solve_fused_batched(K, a, b, cfg, impl="jnp")
        res_err = stream_err = 0.0
        for i in range(B):
            P_ref = np.asarray(sinkhorn_uot_fused(
                K[i], a[i], b[i], cfg32)[0])
            scale = np.abs(P_ref).max()
            res_err = max(res_err, np.abs(
                np.asarray(P[i], np.float32) - P_ref).max() / scale)
            stream_err = max(stream_err, np.abs(
                np.asarray(P_stream[i], np.float32) - P_ref).max() / scale)
        assert res_err <= 2 ** -8  # one bf16 rounding of the final iterate
        assert res_err <= stream_err + 1e-6

    def test_single_problem_2d_entry(self):
        M, N = 40, 200
        K, a, b = make_stack(1, M, N, seed=4)
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=4000, tol=1e-5)
        P, colsum, iters, err = ops.solve_fused_resident(
            K[0], a[0], b[0], cfg, impl="jnp")
        assert P.shape == (M, N) and colsum.shape == (N,)
        P_ref, stats = sinkhorn_uot_fused(K[0], a[0], b[0], cfg)
        np.testing.assert_allclose(np.asarray(P), np.asarray(P_ref),
                                   rtol=2e-6, atol=1e-9)
        assert int(iters) == int(stats["iters"]) < 4000
        assert float(err) <= 1e-5


class TestResidentStepped:
    """LaneState chunk advance: resident chunks == streamed chunks."""

    CFG = UOTConfig(reg=0.1, reg_m=1.0, num_iters=30, tol=1e-3)

    def _pool(self, L=4, M=28, N=130, seed=5, cfg=None):
        cfg = cfg or self.CFG
        K, a, b = make_stack(L, M, N, seed=seed)
        st = ops.make_lane_state(L, M, N, cfg)
        return ops.lane_admit(st, jnp.arange(L), K, a, b)

    @pytest.mark.parametrize("flavor", IMPLS)
    @pytest.mark.parametrize("tol", [None, 1e-3])
    def test_matches_streamed_stepped(self, flavor, tol):
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=30, tol=tol)
        st_s = st_r = self._pool(cfg=cfg)
        interpret = True if flavor == "kernel" else None
        for _ in range(10):
            st_s = ops.solve_fused_stepped(st_s, 4, cfg, impl="jnp")
            st_r = ops.solve_fused_stepped_resident(
                st_r, 4, cfg, impl=flavor, interpret=interpret)
        np.testing.assert_array_equal(np.asarray(st_r.iters),
                                      np.asarray(st_s.iters))
        np.testing.assert_array_equal(np.asarray(st_r.converged),
                                      np.asarray(st_s.converged))
        np.testing.assert_allclose(np.asarray(st_r.P), np.asarray(st_s.P),
                                   rtol=1e-6, atol=1e-9)
        np.testing.assert_allclose(np.asarray(st_r.frow),
                                   np.asarray(st_s.frow),
                                   rtol=1e-6, atol=1e-9)

    def test_chunk_boundary_invariance(self):
        """A lane's answer must not depend on the chunking — including a
        lane that converges mid-chunk and one that is inactive."""
        st0 = self._pool()
        st0 = ops.lane_evict(st0, jnp.int32(2))  # one free lane in the pool
        fine = coarse = st0
        for _ in range(30):
            fine = ops.solve_fused_stepped_resident(
                fine, 1, self.CFG, impl="kernel", interpret=True)
        for _ in range(5):
            coarse = ops.solve_fused_stepped_resident(
                coarse, 6, self.CFG, impl="kernel", interpret=True)
        np.testing.assert_array_equal(np.asarray(fine.iters),
                                      np.asarray(coarse.iters))
        np.testing.assert_allclose(np.asarray(fine.P), np.asarray(coarse.P),
                                   rtol=1e-7, atol=1e-10)
        # the freed lane stayed zero and ran no iterations
        assert not np.asarray(fine.active)[2]
        assert np.asarray(fine.iters)[2] == 0
        assert np.abs(np.asarray(fine.P[2])).max() == 0.0

    def test_finished_bf16_lane_roundtrips_bit_exact(self):
        """The per-chunk up/downcast must be the identity for lanes that
        run zero iterations, whatever the storage dtype — a frozen bf16
        tile crossing a chunk boundary must not pick up a re-rounding."""
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=10, tol=1e-2,
                        dtype=jnp.bfloat16)
        st = self._pool(cfg=cfg)
        for _ in range(10):
            st = ops.solve_fused_stepped_resident(
                st, 5, cfg, impl="kernel", interpret=True)
        done = np.asarray(ops.lane_done(st, cfg.num_iters))
        assert done.all()  # every lane finished: converged or at the cap
        before = np.asarray(st.P).copy()
        st2 = ops.solve_fused_stepped_resident(
            st, 3, cfg, impl="kernel", interpret=True)
        np.testing.assert_array_equal(np.asarray(st2.P), before)
        np.testing.assert_array_equal(np.asarray(st2.iters),
                                      np.asarray(st.iters))


class TestDispatch:
    """resident_fits boundary + impl='auto' routing."""

    CFG = UOTConfig(reg=0.1, reg_m=1.0, num_iters=2)

    def test_fits_boundary_exact(self):
        # fp32 model: Mp*Np*(4*4 + 2*4) + vectors <= 64 MiB. At Np = 1024
        # the largest fitting Mp is 2730 minus the vector overhead rows.
        assert ops.resident_fits(2096, 1024, self.CFG)
        assert not ops.resident_fits(2104, 1024, self.CFG)
        # bf16 storage earns more rows at the same budget (16 B/elt)
        assert ops.resident_fits(2816, 1024, self.CFG,
                                 storage_dtype=jnp.bfloat16)
        assert not ops.resident_fits(2832, 1024, self.CFG,
                                     storage_dtype=jnp.bfloat16)
        # the serving bucket shapes the tier was built for are way inside
        assert ops.resident_fits(256, 384, self.CFG)
        assert ops.resident_fits(256, 384, self.CFG,
                                 storage_dtype=jnp.bfloat16)

    def test_auto_routes_over_budget_problem_to_streamed(self):
        """A problem just over budget must dispatch streamed — and still
        produce the right answer."""
        M, N = 2104, 1024  # just over the fp32 boundary above
        rng = np.random.default_rng(7)
        K = jnp.asarray(rng.uniform(0.1, 1.0, (1, M, N)), jnp.float32)
        a = jnp.asarray(rng.uniform(0.5, 1.5, (1, M)), jnp.float32)
        b = jnp.asarray(rng.uniform(0.5, 1.5, (1, N)), jnp.float32)
        ops.reset_dispatch_stats()
        P_auto, _ = ops.solve_fused_batched(K, a, b, self.CFG, impl="auto")
        assert ops.dispatch_stats() == {"resident": 0, "streamed": 1}
        P_jnp, _ = ops.solve_fused_batched(K, a, b, self.CFG, impl="jnp")
        np.testing.assert_allclose(np.asarray(P_auto), np.asarray(P_jnp),
                                   rtol=1e-6, atol=1e-9)

    def test_auto_routes_fitting_problem_to_resident(self):
        K, a, b = make_stack(2, 24, 130, seed=8)
        ops.reset_dispatch_stats()
        P_auto, cs_auto = ops.solve_fused_batched(K, a, b, self.CFG,
                                                  impl="auto")
        assert ops.dispatch_stats() == {"resident": 1, "streamed": 0}
        P_jnp, cs_jnp = ops.solve_fused_batched(K, a, b, self.CFG,
                                                impl="jnp")
        np.testing.assert_allclose(np.asarray(P_auto), np.asarray(P_jnp),
                                   rtol=1e-6, atol=1e-9)
        # single-problem entry point routes too
        ops.reset_dispatch_stats()
        P1, _ = ops.solve_fused(K[0], a[0], b[0], self.CFG, impl="auto")
        assert ops.dispatch_stats()["resident"] == 1
        np.testing.assert_allclose(np.asarray(P1), np.asarray(P_jnp[0]),
                                   rtol=1e-6, atol=1e-9)

    def test_auto_over_budget_keeps_tol_semantics(self):
        """solve_fused(impl='auto') must honor cfg.tol on BOTH sides of
        the dispatch boundary — the streamed fallback goes through the
        per-lane early-exit path, not the legacy fixed-iteration loop."""
        M, N = 2104, 1024
        rng = np.random.default_rng(11)
        K = jnp.asarray(rng.uniform(0.1, 1.0, (M, N)), jnp.float32)
        a = jnp.asarray(rng.uniform(0.5, 1.5, M), jnp.float32)
        b = jnp.asarray(rng.uniform(0.5, 1.5, N), jnp.float32)
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=50, tol=1e-2)
        P_auto, _ = ops.solve_fused(K, a, b, cfg, impl="auto")
        stats = sinkhorn_uot_fused(K, a, b, cfg)[1]
        assert int(stats["iters"]) < 50  # tol actually fires here
        P_ref, _ = ops.solve_fused_batched(K[None], a[None], b[None], cfg,
                                           impl="jnp")
        np.testing.assert_allclose(np.asarray(P_auto), np.asarray(P_ref[0]),
                                   rtol=1e-6, atol=1e-9)

    def test_explicit_resident_over_budget_raises(self):
        K = jnp.zeros((4096, 4096), jnp.float32)
        with pytest.raises(ValueError, match="VMEM budget"):
            ops.solve_fused(K, jnp.ones(4096), jnp.ones(4096), self.CFG,
                            impl="resident")

    def test_stepped_auto_keeps_bf16_pools_streamed(self):
        """Sub-fp32 pools round per iteration on the streamed path; auto
        must not switch them to per-chunk rounding."""
        cfg32 = UOTConfig(reg=0.1, reg_m=1.0, num_iters=8, tol=1e-3)
        cfg16 = UOTConfig(reg=0.1, reg_m=1.0, num_iters=8, tol=1e-3,
                          dtype=jnp.bfloat16)
        st32 = ops.make_lane_state(2, 24, 130, cfg32)
        st16 = ops.make_lane_state(2, 24, 130, cfg16)
        ops.reset_dispatch_stats()
        ops.solve_fused_stepped(st32, 2, cfg32, impl="auto")
        ops.solve_fused_stepped(st16, 2, cfg16, impl="auto")
        assert ops.dispatch_stats() == {"resident": 1, "streamed": 1}

    def test_bucketed_auto_resolves_per_chunk(self):
        K, a, b = make_stack(2, 24, 100, seed=9)
        problems = [(np.asarray(K[i]), np.asarray(a[i]), np.asarray(b[i]))
                    for i in range(2)]
        res_auto = ops.solve_fused_bucketed(problems, self.CFG, impl="auto")
        res_jnp = ops.solve_fused_bucketed(problems, self.CFG, impl="jnp")
        for (Pa, _), (Pj, _) in zip(res_auto, res_jnp):
            np.testing.assert_allclose(Pa, Pj, rtol=1e-6, atol=1e-9)
