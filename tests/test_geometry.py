"""Implicit cost geometries: mirrors, solver parity, dispatch, serving.

The geometry subsystem's contract is strict: for a point-cloud geometry,
the on-chip tile compute path and the dense path fed by the materializing
mirror produce **bit-identical couplings** (fp32 and bf16 alike) and
identical per-lane iteration counts, across solver tiers (streamed kernel,
jnp, resident, auto) — the tile source is a memory decision, never a math
decision. Grid geometries' per-axis contractions are associativity
*re-orderings* of the dense reductions, so their parity bars are
tolerance-based.

One scoped exception to bitwise-ness, asserted at tolerance instead: a
problem solved standalone vs inside a batch bucket with a *different
padded height* (the resident tier pads M to the sublane, a bucket pads to
its shape) crosses XLA whole-tile reductions of different trip counts,
whose accumulation grouping — and hence low bits — differ. Dense and
implicit stay bit-identical to *each other* at every fixed padded shape.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import UOTConfig, UOTProblem
from repro.core.log_domain import sinkhorn_uot_log
from repro.core.sinkhorn_uv import sinkhorn_uot_uv, sinkhorn_uot_uv_fused
from repro.geometry import (DenseGeometry, Geometry, GridGeometry,
                            PointCloudGeometry)
from repro.kernels import ops

IMPLS = ["kernel", "jnp", "resident", "auto"]
DTYPES = [jnp.float32, jnp.bfloat16]


def make_points(M, N, d=3, seed=0, mass=1.2):
    rng = np.random.default_rng(seed)
    x = rng.uniform(0, 1, (M, d)).astype(np.float32)
    y = rng.uniform(0, 1, (N, d)).astype(np.float32)
    a = (rng.uniform(0.5, 1.5, M) / M).astype(np.float32)
    b = (rng.uniform(0.5, 1.5, N) / N * mass).astype(np.float32)
    return x, y, jnp.asarray(a), jnp.asarray(b)


def solve(geom, a, b, cfg, impl, **kw):
    interpret = True if impl == "kernel" else None
    return ops.solve_fused(None, a, b, cfg, geometry=geom, impl=impl,
                           interpret=interpret, **kw)


class TestGeometryObjects:
    def test_pointcloud_cost_matches_cdist(self):
        x, y, _, _ = make_points(37, 53)
        g = PointCloudGeometry.from_points(x, y, scale=2.0)
        ref = ((x[:, None, :] - y[None, :, :]) ** 2).sum(-1) / 2.0
        np.testing.assert_allclose(np.asarray(g.cost()), ref, atol=1e-5)
        np.testing.assert_allclose(np.asarray(g.kernel(0.1)),
                                   np.exp(-ref / 0.1), atol=1e-5)
        assert g.shape == (37, 53) and g.is_implicit

    def test_pointcloud_valid_mask_zeros(self):
        x, y, _, _ = make_points(32, 48)
        g = PointCloudGeometry.from_points(x, y, m_valid=20, n_valid=30)
        K = np.asarray(g.kernel(0.1))
        assert (K[20:] == 0).all() and (K[:, 30:] == 0).all()
        assert (K[:20, :30] > 0).all()

    def test_masked_geometry_refuses_lazy_and_cost_paths(self):
        """Valid-count masks are a kernel-path construct: kernel() honors
        them, but cost() and the lazy applications must refuse instead of
        silently reducing over the padded coordinates' exp(0)-sized
        entries."""
        x, y, _, _ = make_points(32, 48)
        g = PointCloudGeometry.from_points(x, y, m_valid=20, n_valid=30)
        v = jnp.ones((48,), jnp.float32)
        u = jnp.ones((32,), jnp.float32)
        for call in (lambda: g.cost(),
                     lambda: g.apply_kernel(v, 0.1),
                     lambda: g.apply_kernel_T(u, 0.1),
                     lambda: g.apply_lse(v, 0.1),
                     lambda: g.apply_lse_T(u, 0.1)):
            with pytest.raises(ValueError, match="slice the"):
                call()
        assert np.asarray(g.kernel(0.1)).shape == (32, 48)  # still fine

    def test_pointcloud_dim_mismatch_raises(self):
        with pytest.raises(ValueError, match="coordinate dims"):
            PointCloudGeometry.from_points(np.zeros((4, 3)),
                                           np.zeros((5, 2)))

    def test_grid_mirrors_match_kron(self):
        rng = np.random.default_rng(1)
        Cx = rng.uniform(0, 1, (5, 6)).astype(np.float32)
        Cy = rng.uniform(0, 1, (7, 4)).astype(np.float32)
        g = GridGeometry((jnp.asarray(Cx), jnp.asarray(Cy)))
        assert g.shape == (35, 24)
        Cref = (Cx[:, None, :, None] + Cy[None, :, None, :]).reshape(35, 24)
        np.testing.assert_allclose(np.asarray(g.cost()), Cref, atol=1e-6)
        np.testing.assert_allclose(np.asarray(g.kernel(0.2)),
                                   np.exp(-Cref / 0.2), rtol=1e-5)

    @pytest.mark.parametrize("kind", ["pc", "grid", "dense"])
    def test_lazy_applications_match_dense(self, kind):
        rng = np.random.default_rng(2)
        if kind == "pc":
            x, y, _, _ = make_points(40, 60, seed=2)
            g = PointCloudGeometry.from_points(x, y)
        elif kind == "grid":
            g = GridGeometry((jnp.asarray(rng.uniform(0, 1, (8, 10))
                                          .astype(np.float32)),
                              jnp.asarray(rng.uniform(0, 1, (5, 6))
                                          .astype(np.float32))))
        else:
            g = DenseGeometry(jnp.asarray(rng.uniform(0, 1, (40, 60))
                                          .astype(np.float32)))
        M, N = g.shape
        K = np.asarray(g.kernel(0.2), np.float64)
        C = np.asarray(g.cost(), np.float64)
        v = rng.uniform(size=N).astype(np.float32)
        u = rng.uniform(size=M).astype(np.float32)
        np.testing.assert_allclose(np.asarray(g.apply_kernel(v, 0.2)),
                                   K @ v, rtol=1e-4, atol=1e-7)
        np.testing.assert_allclose(np.asarray(g.apply_kernel_T(u, 0.2)),
                                   u @ K, rtol=1e-4, atol=1e-7)
        zs = (v - 0.5) / 2

        def lse(A, axis):
            m = A.max(axis=axis, keepdims=True)
            return (np.log(np.exp(A - m).sum(axis=axis))
                    + np.squeeze(m, axis))

        np.testing.assert_allclose(np.asarray(g.apply_lse(zs, 0.2)),
                                   lse((zs[None, :] - C) / 0.2, 1),
                                   rtol=1e-4, atol=2e-5)
        zu = (u - 0.5) / 2
        np.testing.assert_allclose(np.asarray(g.apply_lse_T(zu, 0.2)),
                                   lse((zu[:, None] - C) / 0.2, 0),
                                   rtol=1e-4, atol=2e-5)

    def test_geometries_are_jit_transparent_pytrees(self):
        x, y, _, _ = make_points(16, 24)
        g = PointCloudGeometry.from_points(x, y, scale=2.0)
        leaves, treedef = jax.tree_util.tree_flatten(g)
        g2 = jax.tree_util.tree_unflatten(treedef, leaves)
        assert g2.scale == 2.0
        f = jax.jit(lambda geom, v: geom.apply_kernel(v, 0.1))
        v = jnp.ones((24,), jnp.float32)
        np.testing.assert_array_equal(np.asarray(f(g, v)),
                                      np.asarray(f(g2, v)))

    def test_uot_problem_carries_geometry(self):
        x, y, a, b = make_points(20, 30)
        p = UOTProblem.from_points(x, y, a, b, scale=3.0)
        assert p.shape == (20, 30)
        assert isinstance(p.geom(), PointCloudGeometry)
        K = p.initial_coupling(0.1)
        np.testing.assert_array_equal(np.asarray(K),
                                      np.asarray(p.geometry.kernel(0.1)))
        pd = UOTProblem.from_cost(p.cost_matrix(), a, b)
        assert isinstance(pd.geom(), DenseGeometry)
        with pytest.raises(ValueError, match="exactly one"):
            UOTProblem(a=a, b=b)


class TestSolveFusedParity:
    """DenseGeometry(C) vs PointCloudGeometry(x, y) with C = ||x-y||^2:
    identical couplings, bit for bit, across impl x dtype x tol."""

    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("dtype", DTYPES, ids=["fp32", "bf16"])
    @pytest.mark.parametrize("tol", [None, 1e-5])
    def test_bitwise_couplings(self, impl, dtype, tol):
        x, y, a, b = make_points(100, 150, seed=1)
        g = PointCloudGeometry.from_points(x, y, scale=3.0)
        gd = DenseGeometry(g.cost())
        cfg = UOTConfig(reg=0.05, reg_m=1.0, num_iters=40, tol=tol)
        Pd, csd = solve(gd, a, b, cfg, impl, storage_dtype=dtype)
        Pi, csi = solve(g, a, b, cfg, impl, storage_dtype=dtype)
        assert Pd.dtype == Pi.dtype == jnp.dtype(dtype)
        np.testing.assert_array_equal(np.asarray(Pd), np.asarray(Pi))
        np.testing.assert_array_equal(np.asarray(csd), np.asarray(csi))

    @pytest.mark.parametrize("impl", ["kernel", "jnp"])
    def test_bitwise_iteration_counts_resident(self, impl):
        # the resident tier reports per-lane counts: implicit and dense
        # must converge at exactly the same iteration
        x, y, a, b = make_points(64, 96, seed=2)
        g = PointCloudGeometry.from_points(x, y)
        gd = DenseGeometry(g.cost())
        cfg = UOTConfig(reg=0.05, reg_m=1.0, num_iters=200, tol=1e-4)
        interpret = True if impl == "kernel" else None
        Pd, _, itd, errd = ops.solve_fused_resident(
            None, a, b, cfg, geometry=gd, impl=impl, interpret=interpret)
        Pi, _, iti, erri = ops.solve_fused_resident(
            None, a, b, cfg, geometry=g, impl=impl, interpret=interpret)
        assert int(itd) == int(iti) < 200  # tol actually fires
        np.testing.assert_array_equal(np.asarray(Pd), np.asarray(Pi))
        assert float(errd) == float(erri) <= 1e-4

    @pytest.mark.parametrize("impl", IMPLS)
    def test_batched_valid_counts_bitwise_vs_dense(self, impl):
        """A ragged bucket: per-problem valid counts mask the computed
        tiles to the exact zeros of the zero-padded dense stack."""
        rng = np.random.default_rng(3)
        B, d = 3, 3
        xs = rng.uniform(0, 1, (B, 64, d)).astype(np.float32)
        ys = rng.uniform(0, 1, (B, 96, d)).astype(np.float32)
        mv, nv = np.array([64, 40, 25]), np.array([96, 60, 96])
        A = np.zeros((B, 64), np.float32)
        Bm = np.zeros((B, 96), np.float32)
        for k in range(B):
            A[k, :mv[k]] = rng.uniform(0.5, 1.5, mv[k]) / mv[k]
            Bm[k, :nv[k]] = rng.uniform(0.5, 1.5, nv[k]) / nv[k] * 1.1
        g = PointCloudGeometry.from_points(xs, ys, m_valid=mv, n_valid=nv)
        cfg = UOTConfig(reg=0.05, reg_m=1.0, num_iters=25, tol=1e-6)
        K = g.kernel(cfg.reg)      # masked dense stack, same padded shape
        Pd, csd = ops.solve_fused_batched(K, jnp.asarray(A),
                                          jnp.asarray(Bm), cfg, impl=impl,
                                          interpret=True)
        Pi, csi = ops.solve_fused_batched(None, jnp.asarray(A),
                                          jnp.asarray(Bm), cfg, impl=impl,
                                          interpret=True, geometry=g)
        np.testing.assert_array_equal(np.asarray(Pd), np.asarray(Pi))
        np.testing.assert_array_equal(np.asarray(csd), np.asarray(csi))
        for k in range(B):   # the masked region really is exact zeros
            assert (np.asarray(Pi[k, mv[k]:, :]) == 0.0).all()
            assert (np.asarray(Pi[k, :, nv[k]:]) == 0.0).all()

    @pytest.mark.parametrize("impl", ["kernel", "jnp"])
    def test_batched_valid_counts_match_standalone(self, impl):
        """Each bucketed problem equals its standalone solve. Bitwise when
        the padded heights coincide (streamed pads both to the same row
        block); the resident tier pads standalone solves to the sublane
        instead of the bucket, so cross-shape reductions differ in the
        low bits -> asserted at tolerance there (see module docstring)."""
        rng = np.random.default_rng(4)
        B, d = 3, 3
        xs = rng.uniform(0, 1, (B, 64, d)).astype(np.float32)
        ys = rng.uniform(0, 1, (B, 96, d)).astype(np.float32)
        mv, nv = np.array([64, 40, 25]), np.array([96, 60, 96])
        A = np.zeros((B, 64), np.float32)
        Bm = np.zeros((B, 96), np.float32)
        for k in range(B):
            A[k, :mv[k]] = rng.uniform(0.5, 1.5, mv[k]) / mv[k]
            Bm[k, :nv[k]] = rng.uniform(0.5, 1.5, nv[k]) / nv[k] * 1.1
        g = PointCloudGeometry.from_points(xs, ys, m_valid=mv, n_valid=nv)
        cfg = UOTConfig(reg=0.05, reg_m=1.0, num_iters=25, tol=1e-6)
        Pb, _ = ops.solve_fused_batched(None, jnp.asarray(A),
                                        jnp.asarray(Bm), cfg, impl=impl,
                                        interpret=True, geometry=g)
        for k in range(B):
            gk = PointCloudGeometry.from_points(xs[k, :mv[k]],
                                                ys[k, :nv[k]])
            Pk, _ = solve(gk, jnp.asarray(A[k, :mv[k]]),
                          jnp.asarray(Bm[k, :nv[k]]), cfg, impl)
            np.testing.assert_array_equal(
                np.asarray(Pb[k, :mv[k], :nv[k]]), np.asarray(Pk))

    def test_geometry_and_a0_are_exclusive(self):
        x, y, a, b = make_points(16, 24)
        g = PointCloudGeometry.from_points(x, y)
        cfg = UOTConfig(num_iters=2)
        with pytest.raises(ValueError, match="not both"):
            ops.solve_fused(jnp.ones((16, 24)), a, b, cfg, geometry=g)
        with pytest.raises(TypeError, match="Geometry"):
            ops.solve_fused(None, a, b, cfg, geometry=np.ones((16, 24)))


class TestDispatchExpansion:
    """Implicit geometries shrink the resident VMEM working set to the
    coupling, so impl='auto' routes shapes to the resident tier that the
    dense path must stream."""

    CFG = UOTConfig(reg=0.05, reg_m=1.0, num_iters=2)

    def test_implicit_budget_is_wider(self):
        # fp32: dense 24 B/elt vs implicit 16 B/elt against the same
        # budget — 1024x2560 is in the gap
        assert not ops.resident_fits(1024, 2560, self.CFG)
        assert ops.resident_fits(1024, 2560, self.CFG, implicit=True)
        # both agree on clearly-fitting and clearly-over shapes
        assert ops.resident_fits(256, 384, self.CFG, implicit=True)
        assert not ops.resident_fits(4096, 4096, self.CFG, implicit=True)

    def test_auto_routes_implicit_to_resident_where_dense_streams(self):
        M, N = 1024, 2560
        rng = np.random.default_rng(5)
        x = rng.uniform(0, 1, (M, 3)).astype(np.float32)
        y = rng.uniform(0, 1, (N, 3)).astype(np.float32)
        a = jnp.asarray((rng.uniform(0.5, 1.5, M) / M).astype(np.float32))
        b = jnp.asarray((rng.uniform(0.5, 1.5, N) / N).astype(np.float32))
        g = PointCloudGeometry.from_points(x, y)
        ops.reset_dispatch_stats()
        Pi, _ = ops.solve_fused(None, a, b, self.CFG, geometry=g,
                                impl="auto")
        assert ops.dispatch_stats() == {"resident": 1, "streamed": 0}
        ops.reset_dispatch_stats()
        Pd, _ = ops.solve_fused(None, a, b, self.CFG,
                                geometry=DenseGeometry(g.cost()),
                                impl="auto")
        assert ops.dispatch_stats() == {"resident": 0, "streamed": 1}
        np.testing.assert_allclose(np.asarray(Pi), np.asarray(Pd),
                                   rtol=1e-5, atol=1e-10)

    def test_explicit_resident_over_implicit_budget_raises(self):
        M, N = 4096, 4096
        rng = np.random.default_rng(6)
        gbig = PointCloudGeometry.from_points(
            rng.uniform(0, 1, (M, 2)).astype(np.float32),
            rng.uniform(0, 1, (N, 2)).astype(np.float32))
        ab = jnp.ones((M,), jnp.float32) / M
        bb = jnp.ones((N,), jnp.float32) / N
        with pytest.raises(ValueError, match="VMEM budget"):
            ops.solve_fused_resident(None, ab, bb, UOTConfig(num_iters=2),
                                     geometry=gbig)


class TestCoreSolversLazyGeometry:
    def test_uv_solver_geometry_matches_dense(self):
        x, y, a, b = make_points(60, 80, seed=7)
        g = PointCloudGeometry.from_points(x, y)
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=80, tol=1e-7)
        Pd, _, sd = sinkhorn_uot_uv(g.kernel(cfg.reg), a, b, cfg)
        Pg, _, sg = sinkhorn_uot_uv(g, a, b, cfg)
        assert int(sd["iters"]) == int(sg["iters"])
        np.testing.assert_allclose(np.asarray(Pd), np.asarray(Pg),
                                   rtol=1e-4, atol=1e-9)
        Pf, _, _ = sinkhorn_uot_uv_fused(
            g, a, b, UOTConfig(reg=0.1, reg_m=1.0, num_iters=40))
        assert Pf.shape == (60, 80)

    def test_log_solver_geometry_matches_dense(self):
        x, y, a, b = make_points(50, 70, seed=8)
        g = PointCloudGeometry.from_points(x, y)
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=60, tol=1e-7)
        Pd, _, sd = sinkhorn_uot_log(g.cost(), a, b, cfg)
        Pg, _, sg = sinkhorn_uot_log(g, a, b, cfg)
        assert int(sd["iters"]) == int(sg["iters"])
        np.testing.assert_allclose(np.asarray(Pd), np.asarray(Pg),
                                   rtol=1e-4, atol=1e-9)

    def test_grid_solvers_never_need_dense(self):
        rng = np.random.default_rng(9)
        g = GridGeometry((jnp.asarray(rng.uniform(0, 1, (8, 10))
                                      .astype(np.float32)),
                          jnp.asarray(rng.uniform(0, 1, (6, 5))
                                      .astype(np.float32))))
        M, N = g.shape
        a = jnp.asarray((rng.uniform(0.5, 1.5, M) / M).astype(np.float32))
        b = jnp.asarray((rng.uniform(0.5, 1.5, N) / N).astype(np.float32))
        cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=60, tol=1e-7)
        Pd, _, sd = sinkhorn_uot_log(g.cost(), a, b, cfg)
        Pg, fg, sg = sinkhorn_uot_log(g, a, b, cfg)
        assert int(sd["iters"]) == int(sg["iters"])
        np.testing.assert_allclose(np.asarray(Pd), np.asarray(Pg),
                                   rtol=1e-4, atol=1e-9)
        # materialize=False: the whole solve (including the return) stays
        # O(M + N) for a grid geometry
        Pn, (f, gpot), _ = sinkhorn_uot_log(g, a, b, cfg,
                                            materialize=False)
        assert Pn is None and f.shape == (M,) and gpot.shape == (N,)
        Pu_d, _, su_d = sinkhorn_uot_uv(g.kernel(cfg.reg), a, b, cfg)
        Pu_g, _, su_g = sinkhorn_uot_uv(g, a, b, cfg)
        assert int(su_d["iters"]) == int(su_g["iters"])
        np.testing.assert_allclose(np.asarray(Pu_d), np.asarray(Pu_g),
                                   rtol=1e-4, atol=1e-9)


class TestServingGeometry:
    CFG = UOTConfig(reg=0.05, reg_m=1.0, num_iters=30, tol=1e-6)

    def _problems(self):
        out = []
        for s, (M, N) in enumerate([(50, 70), (50, 70), (30, 40),
                                    (50, 70)]):
            x, y, a, b = make_points(M, N, seed=10 + s)
            out.append((x, y, a, b))
        return out

    def test_engine_points_bitwise_vs_dense(self):
        from repro.serve import UOTBatchEngine
        ep = UOTBatchEngine(self.CFG, interpret=True)
        ed = UOTBatchEngine(self.CFG, interpret=True)
        rids = []
        for x, y, a, b in self._problems():
            g = PointCloudGeometry.from_points(x, y)
            rids.append((ep.submit_points(x, y, a, b),
                         ed.submit(np.asarray(g.kernel(self.CFG.reg)),
                                   a, b)))
        rp, rd = ep.flush(), ed.flush()
        assert not ep.pending
        for rid_p, rid_d in rids:
            np.testing.assert_array_equal(np.asarray(rp[rid_p]),
                                          np.asarray(rd[rid_d]))

    def test_scheduler_points_bitwise_vs_dense(self):
        """geometry path through solve_fused_stepped: a coordinate
        request's lane trajectory is bit-identical to dense submission of
        the mirror kernel — same pool, same stepped solves."""
        from repro.serve import UOTScheduler
        sp = UOTScheduler(self.CFG, interpret=True, lanes_per_pool=3)
        sd = UOTScheduler(self.CFG, interpret=True, lanes_per_pool=3)
        rids = []
        for x, y, a, b in self._problems():
            g = PointCloudGeometry.from_points(x, y)
            rids.append((sp.submit_points(x, y, a, b),
                         sd.submit(np.asarray(g.kernel(self.CFG.reg)),
                                   a, b)))
        op_, od = sp.run(), sd.run()
        for rid_p, rid_d in rids:
            np.testing.assert_array_equal(op_[rid_p], od[rid_d])
        itp = {t.rid: t.iters for t in sp.request_log}
        itd = {t.rid: t.iters for t in sd.request_log}
        assert [itp[r] for r, _ in rids] == [itd[r] for _, r in rids]

    def test_scheduler_mixed_dense_and_point_requests_share_pool(self):
        from repro.serve import UOTScheduler
        s = UOTScheduler(self.CFG, interpret=True, lanes_per_pool=4)
        probs = self._problems()
        rid_refs = []
        for i, (x, y, a, b) in enumerate(probs):
            g = PointCloudGeometry.from_points(x, y)
            if i % 2:
                rid = s.submit(np.asarray(g.kernel(self.CFG.reg)), a, b)
            else:
                rid = s.submit_points(x, y, a, b)
            Pref, _ = solve(g, a, b, self.CFG, "jnp")
            rid_refs.append((rid, np.asarray(Pref)))
        out = s.run()
        for rid, Pref in rid_refs:
            np.testing.assert_allclose(out[rid], Pref, rtol=1e-5,
                                       atol=1e-10)

    def test_stepped_lane_admit_geometry_materialization(self):
        """Direct stepped-API check: admitting the device-materialized
        mirror kernel equals admitting the host-shipped dense copy."""
        x, y, a, b = make_points(40, 60, seed=20)
        g = PointCloudGeometry.from_points(x, y)
        K = g.kernel(self.CFG.reg)
        st1 = ops.make_lane_state(2, 64, 128, self.CFG)
        st2 = ops.make_lane_state(2, 64, 128, self.CFG)
        st1 = ops.lane_admit(st1, 0, K, a, b)
        st2 = ops.lane_admit(st2, 0, jnp.asarray(np.asarray(K)), a, b)
        for _ in range(3):
            st1 = ops.solve_fused_stepped(st1, 4, self.CFG, impl="jnp")
            st2 = ops.solve_fused_stepped(st2, 4, self.CFG, impl="jnp")
        np.testing.assert_array_equal(np.asarray(st1.P),
                                      np.asarray(st2.P))
        np.testing.assert_array_equal(np.asarray(st1.iters),
                                      np.asarray(st2.iters))
