"""Subprocess body for distributed-solver tests (8 forced host devices).

Run as:  XLA flags are set HERE, before jax import — pytest invokes this in
a fresh interpreter so the main test process keeps its single device.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import UOTConfig, sinkhorn_uot_fused  # noqa: E402
from repro.core.distributed import (  # noqa: E402
    rowsharded_fused_solver, sharded2d_fused_solver,
    rowsharded_overlapped_solver, shard_inputs)
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402


def make_problem(M=128, N=96, reg=0.1, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(M, 2)).astype(np.float32)
    Y = rng.normal(size=(N, 2)).astype(np.float32) + 0.5
    C = ((X[:, None, :] - Y[None, :, :]) ** 2).sum(-1)
    C = C / C.max()
    a = rng.uniform(0.5, 1.5, size=M).astype(np.float32)
    b = rng.uniform(0.5, 1.5, size=N).astype(np.float32)
    a, b = a / a.sum(), b / b.sum() * 1.3
    K = np.exp(-C / reg) * (a[:, None] * b[None, :])
    return jnp.asarray(K), jnp.asarray(a), jnp.asarray(b)


def main():
    assert jax.device_count() == 8, jax.device_count()
    K, a, b = make_problem()
    cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=60)
    ref, _ = sinkhorn_uot_fused(K, a, b, cfg)
    ref = np.asarray(ref)

    # --- 1-D row-sharded (the paper's MPI design) over all 8 devices ------
    mesh = jax.make_mesh((8,), ("rows",))
    solver = rowsharded_fused_solver(mesh, "rows", cfg)
    sA, sa, sb = shard_inputs(mesh, "rows", K, a, b)
    A1, colsum, iters = solver(sA, sa, sb)
    assert int(iters) == cfg.num_iters, int(iters)
    np.testing.assert_allclose(np.asarray(A1), ref, rtol=3e-5, atol=1e-8)
    np.testing.assert_allclose(np.asarray(colsum), ref.sum(0), rtol=3e-4)
    print("rowsharded: OK")

    # --- 2-D sharded (beyond paper) over a 4x2 mesh -----------------------
    mesh2 = jax.make_mesh((4, 2), ("r", "c"))
    solver2 = sharded2d_fused_solver(mesh2, "r", "c", cfg)
    sA = jax.device_put(K, NamedSharding(mesh2, P("r", "c")))
    sa = jax.device_put(a, NamedSharding(mesh2, P("r")))
    sb = jax.device_put(b, NamedSharding(mesh2, P("c")))
    A2, _ = solver2(sA, sa, sb)
    np.testing.assert_allclose(np.asarray(A2), ref, rtol=3e-5, atol=1e-8)
    print("sharded2d: OK")

    # --- overlapped ring-reduce variant ------------------------------------
    solver3 = rowsharded_overlapped_solver(mesh, "rows", cfg, num_chunks=4)
    sA, sa, sb = shard_inputs(mesh, "rows", K, a, b)
    A3, _ = solver3(sA, sa, sb)
    np.testing.assert_allclose(np.asarray(A3), ref, rtol=3e-5, atol=1e-8)
    print("overlapped: OK")

    # --- collective volume sanity: HLO contains exactly the expected ops ---
    lowered = jax.jit(solver.__wrapped__ if hasattr(solver, "__wrapped__")
                      else solver).lower(sA, sa, sb)
    hlo = lowered.compile().as_text()
    assert "all-reduce" in hlo, "expected an all-reduce (MPI_Allreduce analog)"
    print("hlo: OK")

    # --- bf16 storage / fp32 reduction on every distributed variant --------
    # The advertised mixed-precision mode, now asserted: blocks stored
    # bf16, psums fp32. The error bar is the documented streamed-bf16
    # pointwise bar (tests/test_bf16_accumulation.py: error saturates well
    # under 5e-2 relative to the coupling scale).
    from repro.core.distributed import gang_solve
    bf16 = jnp.bfloat16
    scale = float(np.abs(ref).max())
    bar = 5e-2 * scale
    builders = [
        ("rowsharded", lambda: rowsharded_fused_solver(
            mesh, "rows", cfg, storage_dtype=bf16), mesh, "1d"),
        ("sharded2d", lambda: sharded2d_fused_solver(
            mesh2, "r", "c", cfg, storage_dtype=bf16), mesh2, "2d"),
        ("overlapped", lambda: rowsharded_overlapped_solver(
            mesh, "rows", cfg, num_chunks=4, storage_dtype=bf16),
         mesh, "1d"),
    ]
    for name, build, m, kind in builders:
        solver16 = build()
        if kind == "1d":
            sA16, sa16, sb16 = shard_inputs(m, "rows", K, a, b)
        else:
            sA16 = jax.device_put(K, NamedSharding(m, P("r", "c")))
            sa16 = jax.device_put(a, NamedSharding(m, P("r")))
            sb16 = jax.device_put(b, NamedSharding(m, P("c")))
        A16, cs16 = solver16(sA16, sa16, sb16)[:2]
        assert A16.dtype == bf16, (name, A16.dtype)
        assert cs16.dtype == jnp.float32, (name, cs16.dtype)
        err = float(np.abs(np.asarray(A16, np.float32) - ref).max())
        assert err <= bar, (name, err, bar)
        print(f"bf16 {name}: OK (max abs err {err:.2e} <= {bar:.2e})")

    # --- gang_solve serving adapter: padding + cache + bf16 ----------------
    # M=100 does not divide 8: the adapter zero-pads rows (exact no-ops),
    # shards, and trims — so any request shape can ride the gang.
    K100, a100 = np.asarray(K)[:100], np.asarray(a)[:100]
    Pg, csg = gang_solve(mesh, "rows", K100, a100, np.asarray(b), cfg)
    refg, _ = sinkhorn_uot_fused(jnp.asarray(K100), jnp.asarray(a100), b,
                                 cfg)
    np.testing.assert_allclose(Pg, np.asarray(refg), rtol=3e-5, atol=1e-8)
    Pg16, _ = gang_solve(mesh, "rows", K100, a100, np.asarray(b), cfg,
                         storage_dtype=bf16)
    err = float(np.abs(Pg16.astype(np.float32)
                       - np.asarray(refg)).max())
    assert err <= 5e-2 * float(np.abs(np.asarray(refg)).max())
    print("gang_solve: OK (padded rows, fp32 + bf16)")

    # overlapped gang: M=100 pads to 8*4=32-row multiples (128), so every
    # local chunk loop covers its whole block — the tail rows a mesh-only
    # pad would leave unrescaled (regression: silently wrong colsums)
    Pgo, _ = gang_solve(mesh, "rows", K100, a100, np.asarray(b), cfg,
                        overlapped=True, num_chunks=4)
    np.testing.assert_allclose(Pgo, np.asarray(refg), rtol=3e-5, atol=1e-8)
    print("gang_solve overlapped: OK (chunk-divisible row padding)")


if __name__ == "__main__":
    main()
    print("DISTRIBUTED_CHECK_PASSED")
