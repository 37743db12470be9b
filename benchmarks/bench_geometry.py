"""Implicit vs dense cost geometries at serving shapes.

Compares, for a B-stack of point-cloud UOT problems at the bucketed
serving shape (256x384-class, PR 1-3's workload):

  * ``dense_e2e``     — the historical serving pipeline: materialize the
                        squared-Euclidean cost + Gibbs kernel on the HOST
                        (numpy, the POT-style preprocessing), ship the
                        ``B*M*N`` stack to the device, solve.
  * ``implicit_e2e``  — ship ``B*(M+N)*(d+1)`` coordinate floats, hand
                        ``solve_fused_batched`` a ``PointCloudGeometry``;
                        cost tiles are evaluated on-device (on-chip in
                        VMEM on the TPU kernel path), the cost matrix
                        never exists in HBM.

Both run ``impl='auto'`` so the serving shape lands on the resident tier
— which is also where the implicit win compounds: the implicit VMEM
budget is coupling-only (``resident_fits(implicit=True)``), so shapes the
dense path must stream (1024x2560 fp32) run resident under a geometry,
measured below as ``residentfit_*``.

Hard in-bench asserts (the ISSUE-4 acceptance):
  * parity — the implicit path's couplings equal the dense-mirror path's
    bit-for-bit in fp32;
  * memory model — the implicit solve's operand set contains NOTHING
    M*N-sized (largest operand is O((M+N)*d) coordinates; asserted
    against the actual arrays handed to the jit), while the dense path's
    smallest possible cost operand is ``B*M*N*4`` bytes;
  * dispatch — ``impl='auto'`` routes 1024x2560 fp32 to the resident tier
    under the implicit geometry and to the streamed tier dense.

Wall-clock honesty (measured, CPU, fp32, tol-converged ~12-iteration
serving solves): the ISSUE-4 expectation was >=1.3x e2e "from halved
read traffic", but on a CPU-only backend the host->device "transfer" is
a memcpy and the read-traffic savings the geometry buys (the kernel
path's on-chip tiles) are exactly the part CPU cannot express — the
measured e2e delta is the host-materialization slice (~4-7 ms of numpy
cost+exp per 16-problem flush, whether via the gemm trick or POT-style
scipy cdist) against a ~25 ms solve, i.e. ~1.0-1.3x and within this
host's scheduler noise. It is emitted as ``geometry_acceptance_fp32``
with that caveat; the claims that survive ANY backend are asserted
structurally instead (bitwise parity, 38x request-payload cut, zero
M*N-sized solve operands), the resident-fit expansion is measured at
~1.2-2x below, and the bandwidth win proper is a TPU-hardware follow-on
(ROADMAP). The grid-geometry records measure the separable-cost path of
``sinkhorn_uot_uv``: per-axis factor contractions vs dense-K matvecs
(~13-15x at 48x48 grids), which also never form M*N.

``BENCH_GEOMETRY_SMOKE=1`` shrinks the cases to a seconds-long CI run.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import UOTConfig
from repro.core.sinkhorn_uv import sinkhorn_uot_uv
from repro.geometry import DenseGeometry, GridGeometry, PointCloudGeometry
from repro.kernels import ops
from benchmarks.common import time_fn, emit


def best_of(fn, reps=9, warmup=2):
    """Best-of-N wall time: the right statistic for an e2e comparison on
    a shared/noisy CPU host, where the median still soaks up scheduler
    interference an order of magnitude above the effect being measured."""
    import time
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def make_clouds(B, M, N, d=3, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.uniform(0, 1, (B, M, d)).astype(np.float32)
    ys = rng.uniform(0, 1, (B, N, d)).astype(np.float32)
    a = rng.uniform(0.5, 1.5, (B, M)).astype(np.float32)
    a /= a.sum(1, keepdims=True)
    b = rng.uniform(0.5, 1.5, (B, N)).astype(np.float32)
    b = b / b.sum(1, keepdims=True) * 1.2
    return xs, ys, a, b


def _mb(nbytes):
    return nbytes / 1e6


def bench_serving_case(B, M, N, d, tol):
    tag = f"B{B}_{M}x{N}_d{d}"
    xs, ys, a, b = make_clouds(B, M, N, d)
    aj, bj = jnp.asarray(a), jnp.asarray(b)
    cfg = UOTConfig(reg=0.05, reg_m=1.0, num_iters=100, tol=tol)
    scale = float(d)  # unit-cube bound ||x - y||^2 <= d
    geom = PointCloudGeometry.from_points(xs, ys, scale=scale)

    def dense_e2e():
        # host materialization (numpy), then ship the B*M*N stack
        Ks = np.empty((B, M, N), np.float32)
        for k in range(B):
            xn = (xs[k] ** 2).sum(1)[:, None]
            yn = (ys[k] ** 2).sum(1)[None, :]
            Ks[k] = np.exp(-((xn + yn - 2.0 * xs[k] @ ys[k].T) / scale)
                           / cfg.reg)
        return ops.solve_fused_batched(jnp.asarray(Ks), aj, bj, cfg,
                                       impl="auto")[0]

    def implicit_e2e():
        # ship coordinates; reuse the geometry's precomputed norms (what a
        # serving stack caches per request at submit)
        gg = PointCloudGeometry(x=jnp.asarray(xs), y=jnp.asarray(ys),
                                xn=geom.xn, yn=geom.yn, scale=scale)
        return ops.solve_fused_batched(None, aj, bj, cfg, impl="auto",
                                       geometry=gg)[0]

    # ---- memory model: the implicit solve's operands are O((M+N)*d);
    # nothing M*N-sized exists before the coupling itself. The dense
    # path's cost operand alone is B*M*N*4 bytes.
    coord_bytes = sum(int(np.prod(s.shape)) * 4
                      for s in (geom.x, geom.y, geom.xn, geom.yn))
    dense_cost_bytes = B * M * N * 4
    assert coord_bytes == B * (M + N) * (d + 1) * 4
    largest_operand = max(int(np.prod(s.shape))
                          for s in (geom.x, geom.y, geom.xn, geom.yn))
    assert largest_operand < M * N, (largest_operand, M * N)

    # ---- parity: implicit == dense-mirror, bit for bit (fp32). (The
    # host-numpy baseline above reproduces the mirror's arithmetic only
    # to float tolerance — gemm vs unrolled dot — so the bitwise assert
    # runs against DenseGeometry(geometry.cost()); the e2e baseline is
    # additionally checked at float tolerance.)
    P_impl = implicit_e2e()
    P_mirror = ops.solve_fused_batched(
        None, aj, bj, cfg, impl="auto",
        geometry=DenseGeometry(geom.cost()))[0]
    assert (np.asarray(P_impl) == np.asarray(P_mirror)).all(), \
        "implicit path diverged from the dense-mirror path"
    P_dense = dense_e2e()
    scale_p = np.abs(np.asarray(P_dense)).max()
    max_rel = (np.abs(np.asarray(P_dense) - np.asarray(P_impl)).max()
               / scale_p)
    assert max_rel < 1e-4, max_rel

    t_dense = best_of(dense_e2e)
    t_impl = best_of(implicit_e2e)
    emit(f"geometry_dense_e2e_{tag}", t_dense * 1e6,
         f"ship_mb={_mb(dense_cost_bytes):.2f},host_materialize=True")
    emit(f"geometry_implicit_e2e_{tag}", t_impl * 1e6,
         f"ship_mb={_mb(coord_bytes):.3f},transfer_cut="
         f"{dense_cost_bytes / coord_bytes:.0f}x,"
         f"speedup={t_dense / t_impl:.2f}x,bitwise_parity=True")
    return t_dense / t_impl


def bench_resident_fit_expansion(smoke):
    """The implicit VMEM budget is coupling-only: 1024x2560 fp32 streams
    dense (24 B/elt > budget) but runs resident implicit (16 B/elt)."""
    M, N = (256, 512) if smoke else (1024, 2560)
    cfg = UOTConfig(reg=0.05, reg_m=1.0, num_iters=10)
    rng = np.random.default_rng(1)
    g = PointCloudGeometry.from_points(
        rng.uniform(0, 1, (M, 3)).astype(np.float32),
        rng.uniform(0, 1, (N, 3)).astype(np.float32), scale=3.0)
    a = jnp.asarray((rng.uniform(0.5, 1.5, M) / M).astype(np.float32))
    b = jnp.asarray((rng.uniform(0.5, 1.5, N) / N).astype(np.float32))
    if not smoke:
        # the acceptance dispatch assert: same shape, same budget — the
        # implicit geometry is what moves it across the resident boundary
        assert not ops.resident_fits(M, N, cfg)
        assert ops.resident_fits(M, N, cfg, implicit=True)
        ops.reset_dispatch_stats()
        ops.solve_fused(None, a, b, cfg, impl="auto", geometry=g)
        assert ops.dispatch_stats() == {"resident": 1, "streamed": 0}
        ops.reset_dispatch_stats()
        ops.solve_fused(None, a, b, cfg, impl="auto",
                        geometry=DenseGeometry(g.cost()))
        assert ops.dispatch_stats() == {"resident": 0, "streamed": 1}

    gd = DenseGeometry(g.cost())
    t_impl = time_fn(lambda: ops.solve_fused(None, a, b, cfg, impl="auto",
                                             geometry=g)[0])
    t_dense = time_fn(lambda: ops.solve_fused(None, a, b, cfg,
                                              impl="auto",
                                              geometry=gd)[0])
    emit(f"residentfit_implicit_{M}x{N}", t_impl * 1e6,
         f"tier=resident,per_solve_coupling_mb={_mb(2 * M * N * 4):.1f}")
    emit(f"residentfit_dense_{M}x{N}", t_dense * 1e6,
         f"tier={'resident' if smoke else 'streamed'},"
         f"speedup_implicit={t_dense / t_impl:.2f}x")


def bench_grid(smoke):
    """Separable grid cost: per-axis contractions vs dense-K matvecs in
    the u/v solver — the geometry never forms M*N at all."""
    n = 16 if smoke else 48
    rng = np.random.default_rng(2)
    Cx = rng.uniform(0, 1, (n, n)).astype(np.float32)
    Cy = rng.uniform(0, 1, (n, n)).astype(np.float32)
    g = GridGeometry((jnp.asarray(Cx), jnp.asarray(Cy)))
    M, N = g.shape
    a = jnp.asarray((rng.uniform(0.5, 1.5, M) / M).astype(np.float32))
    b = jnp.asarray((rng.uniform(0.5, 1.5, N) / N * 1.1)
                    .astype(np.float32))
    cfg = UOTConfig(reg=0.2, reg_m=1.0, num_iters=20)
    K = g.kernel(cfg.reg)

    P_d, _, _ = sinkhorn_uot_uv(K, a, b, cfg)
    P_g, _, _ = sinkhorn_uot_uv(g, a, b, cfg)
    rel = (np.abs(np.asarray(P_d) - np.asarray(P_g)).max()
           / np.abs(np.asarray(P_d)).max())
    assert rel < 1e-4, rel

    t_dense = time_fn(lambda: sinkhorn_uot_uv(K, a, b, cfg)[0])
    t_grid = time_fn(lambda: sinkhorn_uot_uv(g, a, b, cfg)[0])
    flop_dense = 2 * M * N                 # per matvec pair, elements
    flop_grid = n * n * (n + n)            # two per-axis contractions
    emit(f"grid_uv_dense_{M}x{N}", t_dense * 1e6,
         f"kernel_mb={_mb(M * N * 4):.1f},matvec_elts={flop_dense}")
    emit(f"grid_uv_factored_{M}x{N}", t_grid * 1e6,
         f"kernel_mb={_mb((n * n * 2) * 4):.3f},matvec_elts={flop_grid},"
         f"speedup={t_dense / t_grid:.1f}x,never_forms_MN=True")


def run():
    smoke = bool(os.environ.get("BENCH_GEOMETRY_SMOKE"))
    if smoke:
        ratio = bench_serving_case(4, 64, 128, 3, tol=1e-4)
    else:
        ratio = bench_serving_case(16, 256, 384, 3, tol=1e-4)
        bench_serving_case(16, 256, 384, 8, tol=1e-4)
        emit("geometry_acceptance_fp32", ratio,
             "bar>=1.3x_e2e;cpu_delta_is_host_materialization_only_"
             "see_docstring;structural_asserts=bitwise_parity+"
             "no_MN_operands+resident_fit_expansion")
    bench_resident_fit_expansion(smoke)
    bench_grid(smoke)
