"""Subprocess body for cluster-runtime tests on 8 forced host devices.

XLA flags must be set before jax import (device count locks at first
init), so pytest runs this in a fresh interpreter — see
tests/test_cluster_distributed.py. Asserts the acceptance property of the
cluster tier: on a REAL 8-device mesh, the shard_map'd cluster scheduler's
results are bit-identical per request to a single-device ``UOTScheduler``
run of the same trace — across placement policies, step modes, and the
per-device-loop oracle.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import UOTConfig, sinkhorn_uot_fused  # noqa: E402
from repro.serve import UOTScheduler  # noqa: E402
from repro.cluster import (ClusterScheduler, cluster_admit,  # noqa: E402
                           cluster_mesh, cluster_stepped,
                           make_cluster_lane_state)
from repro.kernels import ops  # noqa: E402


def make_problem(m, n, seed, peak=1.0, reg=0.1):
    r = np.random.default_rng(seed)
    C = r.uniform(0, 1, (m, n)).astype(np.float32) * peak
    a = r.uniform(0.5, 1.5, m).astype(np.float32)
    b = r.uniform(0.5, 1.5, n).astype(np.float32)
    a, b = a / a.sum(), b / b.sum() * 1.2
    return np.exp(-C / reg) * (a[:, None] * b[None, :]), a, b


def workload(seed, n_requests=16):
    r = np.random.default_rng(seed)
    shapes = [(8, 100), (20, 128), (32, 64), (16, 90), (24, 120)]
    return [make_problem(*shapes[r.integers(len(shapes))],
                         seed=seed * 1000 + i,
                         peak=float(r.uniform(1.0, 8.0)))
            for i in range(n_requests)]


def check_sharded_advance_bit_identity(mesh, cfg):
    """One shard_map launch == per-device loop == single-device pool."""
    K, a, b = make_problem(30, 100, 7, peak=4.0)
    st = ops.lane_admit(ops.make_lane_state(2, 32, 128, cfg),
                        jnp.int32(0), jnp.asarray(K), jnp.asarray(a),
                        jnp.asarray(b))
    cs = make_cluster_lane_state(8, 2, 32, 128, cfg, mesh=mesh)
    cs = cluster_admit(cs, jnp.int32(5), jnp.int32(0), jnp.asarray(K),
                       jnp.asarray(a), jnp.asarray(b))
    cs_loop = cs
    for _ in range(12):
        st = ops.solve_fused_stepped(st, 4, cfg, impl="jnp")
        cs = cluster_stepped(cs, 4, cfg, mesh=mesh, impl="jnp")
        cs_loop = cluster_stepped(cs_loop, 4, cfg, mesh=None, impl="jnp")
    assert np.array_equal(np.asarray(cs.lanes.P)[5, 0], np.asarray(st.P[0]))
    assert int(np.asarray(cs.lanes.iters)[5, 0]) == int(st.iters[0])
    for a_leaf, b_leaf in zip(jax.tree_util.tree_leaves(cs),
                              jax.tree_util.tree_leaves(cs_loop)):
        assert np.array_equal(np.asarray(a_leaf), np.asarray(b_leaf))
    print("sharded advance bit-identity: OK")


def check_scheduler_bit_identity(mesh, cfg):
    """The acceptance property: every request's coupling from the 8-device
    mesh scheduler equals the single-device UOTScheduler's, bit for bit,
    for every placement policy and step mode."""
    probs = workload(3)
    ref = UOTScheduler(cfg, lanes_per_pool=2, chunk_iters=3, m_bucket=32,
                       impl="jnp")
    rids = [ref.submit(*p) for p in probs]
    ref_out = ref.run()
    expected = [ref_out[r] for r in rids]
    for kwargs in [dict(placement="least_loaded", step_mode="sync"),
                   dict(placement="bucket_affinity", step_mode="sync"),
                   dict(placement="least_loaded", step_mode="async")]:
        cs = ClusterScheduler(cfg, mesh=mesh, lanes_per_device=2,
                              chunk_iters=3, m_bucket=32, impl="jnp",
                              **kwargs)
        crids = [cs.submit(*p) for p in probs]
        out = cs.run()
        assert cs.pending == 0 and cs.in_flight == 0
        for cr, expect in zip(crids, expected):
            assert np.array_equal(out[cr], expect), kwargs
        st = cs.stats()
        assert st["completed"] == len(probs)
        assert sum(v["completed"] for v in st["devices"].values()) \
            == len(probs)
        print(f"scheduler bit-identity {kwargs}: OK "
              f"(devices used: "
              f"{sum(1 for v in st['devices'].values() if v['placed'])})")


def check_points_requests(mesh, cfg):
    """Coordinate payloads through the mesh == dense submission."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(24, 3)).astype(np.float32)
    y = rng.normal(size=(100, 3)).astype(np.float32) + 0.3
    a = rng.uniform(0.5, 1.5, 24).astype(np.float32)
    b = rng.uniform(0.5, 1.5, 100).astype(np.float32)
    a, b = a / a.sum(), b / b.sum() * 1.2
    from repro.geometry import PointCloudGeometry
    g = PointCloudGeometry.from_points(x, y, scale=2.0)
    dense = ClusterScheduler(cfg, mesh=mesh, lanes_per_device=2,
                             m_bucket=32, impl="jnp")
    rd = dense.submit(np.asarray(g.kernel(cfg.reg)), a, b)
    pts = ClusterScheduler(cfg, mesh=mesh, lanes_per_device=2,
                           m_bucket=32, impl="jnp")
    rp = pts.submit_points(x, y, a, b, scale=2.0)
    assert np.array_equal(dense.run()[rd], pts.run()[rp])
    print("points == dense through the mesh: OK")


def check_gang_escape_hatch(mesh, cfg):
    """Over-budget requests run on the row-sharded gang across the same
    mesh the lane pools shard over — one submit API, two tiers."""
    cs = ClusterScheduler(cfg, mesh=mesh, lanes_per_device=2, impl="jnp",
                          lane_budget=lambda Mb, Nb: Mb * Nb <= 64 * 128)
    small = make_problem(16, 100, 11)
    Kb, ab, bb = make_problem(300, 256, 12)
    r_small = cs.submit(*small)
    r_gang = cs.submit(Kb, ab, bb)
    out = cs.run()
    assert r_small in out and r_gang in out
    # the gang honours cfg.tol, with the one-device solve's rule
    ref, _ = sinkhorn_uot_fused(jnp.asarray(Kb), jnp.asarray(ab),
                                jnp.asarray(bb), cfg)
    np.testing.assert_allclose(out[r_gang], np.asarray(ref), rtol=3e-5,
                               atol=1e-8)
    st = cs.stats()
    assert st["gang_completed"] == 1 and st["router"]["gang_routed"] == 1
    print("gang escape hatch on the mesh: OK")


def main():
    assert jax.device_count() == 8, jax.device_count()
    cfg = UOTConfig(reg=0.1, reg_m=1.0, num_iters=40, tol=1e-3)
    mesh = cluster_mesh(8)
    check_sharded_advance_bit_identity(mesh, cfg)
    check_scheduler_bit_identity(mesh, cfg)
    check_points_requests(mesh, cfg)
    check_gang_escape_hatch(mesh, cfg)


if __name__ == "__main__":
    main()
    print("CLUSTER_CHECK_PASSED")
