"""Device-sharded lane pools: every device's solver lanes in one launch.

``repro.serve.scheduler`` advances one device's ``ops.LaneState`` pool per
chunk. This module stacks D such pools along a leading *device* axis into a
``ClusterLaneState`` and advances ALL of them in ONE ``shard_map``-ped
stepped launch: each mesh device holds its own (L, Mp, Np) slice and runs
exactly the single-device chunk program on it, with **zero collectives** —
per-lane math never crosses lanes, so it certainly never crosses devices.
The only cross-device traffic in the whole serving loop is admission
payloads routed to the owning shard and the O(D*L) lifecycle flags the host
reads between chunks.

Correctness contract (what makes a cluster of lane pools serveable at all):
per-lane math is arrival-order / occupancy / placement invariant — a
problem's trajectory is a function of its own (K, a, b) alone — so WHICH
device and lane a request lands on cannot change its result. The
per-device block the shard_map body sees has the same shape and runs the
same ops as a single-device pool of L lanes, making cluster results
bit-identical to the single-device scheduler's (property-tested, and
asserted request-by-request in tests/_cluster_check.py on 8 forced host
devices).

Two advance modes:

* ``cluster_stepped(..., mesh=mesh)`` — the production form: one
  ``shard_map`` launch over the mesh axis advances every device's pool.
* ``cluster_stepped(..., mesh=None)`` — the degenerate/simulation form for
  single-device hosts (and the bit-identity oracle): a Python loop of D
  per-device launches, each *identical* in shape and program to the
  single-device scheduler's pool advance.

``lane_admit``'s ``m_valid`` / ``n_valid`` masking carries over:
``cluster_admit`` records each lane's live extent, so one physical pool can
host lanes of several padded shapes (the router's cross-bucket sharing
path).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.problem import UOTConfig
from repro.kernels import ops


@dataclasses.dataclass
class ClusterLaneState:
    """D stacked lane pools: a ``LaneState`` whose every field carries a
    leading (D,) device axis (P is (D, L, Mp, Np), iters (D, L), ...).

    A registered pytree. With a mesh the leaves are placed sharded along
    the device axis (``make_cluster_lane_state(mesh=...)``), so the
    ``shard_map`` advance touches only device-local bytes; without one the
    leading axis is an ordinary batch dimension (simulation mode).
    """

    lanes: ops.LaneState

    @property
    def num_devices(self) -> int:
        return self.lanes.P.shape[0]

    @property
    def lanes_per_device(self) -> int:
        return self.lanes.P.shape[1]

    def device_state(self, d: int) -> ops.LaneState:
        """Device ``d``'s pool as a plain single-device ``LaneState``."""
        return jax.tree_util.tree_map(lambda x: stack_get(x, d), self.lanes)


jax.tree_util.register_dataclass(
    ClusterLaneState, data_fields=["lanes"], meta_fields=[])


def cluster_mesh(num_devices: int | None = None,
                 axis: str = "devices") -> Mesh:
    """1-D mesh over the first ``num_devices`` local devices (default all)."""
    n = jax.device_count() if num_devices is None else num_devices
    return jax.make_mesh((n,), (axis,))


def make_cluster_lane_state(num_devices: int, lanes_per_device: int, M: int,
                            N: int, cfg: UOTConfig, *, mesh: Mesh | None = None,
                            axis: str = "devices", block_m: int | None = None,
                            storage_dtype=None) -> ClusterLaneState:
    """Empty D-device pool stack for problems of (padded) shape up to (M, N).

    Built by stacking ``ops.make_lane_state`` D times, so every device's
    slice has exactly the single-device pool's padded shape (the
    bit-identity anchor). With ``mesh`` the stack is placed sharded along
    ``axis`` (one pool slice resident per device).
    """
    st = ops.make_lane_state(lanes_per_device, M, N, cfg, block_m=block_m,
                             storage_dtype=storage_dtype)
    lanes = jax.tree_util.tree_map(
        lambda x: jnp.repeat(x[None], num_devices, axis=0), st)
    if mesh is not None:
        if mesh.shape[axis] != num_devices:
            raise ValueError(f"mesh axis {axis!r} has {mesh.shape[axis]} "
                             f"devices, want {num_devices}")
        sharding = NamedSharding(mesh, P(axis))
        lanes = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), lanes)
    return ClusterLaneState(lanes=lanes)


def stack_set(x: jax.Array, idx, value) -> jax.Array:
    """``x.at[idx].set(value)`` that keeps ``x``'s sharding: on a mesh the
    pool stack is sharded along the device axis, and a scatter into it
    must name its output sharding."""
    return x.at[idx].set(value, out_sharding=jax.typeof(x).sharding)


def stack_get(x: jax.Array, idx) -> jax.Array:
    """``x[idx]`` of a pool-stack leaf. On a mesh the stack is sharded
    along the device axis, so the gathered slice must name its sharding:
    it comes back replicated."""
    sharding = jax.typeof(x).sharding
    if sharding.mesh.empty:
        return x[idx]
    return x.at[idx].get(out_sharding=NamedSharding(sharding.mesh, P()))


@jax.jit
def cluster_admit(cstate: ClusterLaneState, device, lane, K: jax.Array,
                  a: jax.Array, b: jax.Array, m_valid=None,
                  n_valid=None) -> ClusterLaneState:
    """Load problem(s) into (device, lane) slot(s) of the stacked pools.

    ``device`` / ``lane`` are traced ints (K (M, N)) or (k,) int vectors
    (K (k, M, N)) — a whole scheduling round's admissions across ALL
    devices land in one update. Payload padding/masking and the
    stored-matrix colsum initialization are shared with ``ops.lane_admit``
    (same helper), so a cluster lane's trajectory is bit-identical to the
    same problem admitted into a single-device pool.
    """
    st = cstate.lanes
    Mp, Np = st.P.shape[2:]
    Kp, ap, bp, mv, nv = ops._pad_admit_payload(Mp, Np, K, a, b, m_valid,
                                                n_valid, st.P.dtype)
    idx = (device, lane)
    return ClusterLaneState(lanes=ops.LaneState(
        P=stack_set(st.P, idx, Kp),
        colsum=stack_set(st.colsum, idx, Kp.astype(jnp.float32).sum(-2)),
        a=stack_set(st.a, idx, ap),
        b=stack_set(st.b, idx, bp),
        frow=stack_set(st.frow, idx, 1.0),
        iters=stack_set(st.iters, idx, 0),
        converged=stack_set(st.converged, idx, False),
        active=stack_set(st.active, idx, True),
        m_valid=stack_set(st.m_valid, idx, mv),
        n_valid=stack_set(st.n_valid, idx, nv),
        healthy=stack_set(st.healthy, idx, True)))


@jax.jit
def cluster_evict(cstate: ClusterLaneState, device, lane) -> ClusterLaneState:
    """Free (device, lane) slot(s): zero the problems, drop the flags —
    one update however many lanes retire across however many devices."""
    st = cstate.lanes
    idx = (device, lane)
    return ClusterLaneState(lanes=ops.LaneState(
        P=stack_set(st.P, idx, jnp.zeros(st.P.shape[2:], st.P.dtype)),
        colsum=stack_set(st.colsum, idx, 0.0),
        a=stack_set(st.a, idx, 0.0),
        b=stack_set(st.b, idx, 0.0),
        frow=stack_set(st.frow, idx, 1.0),
        iters=stack_set(st.iters, idx, 0),
        converged=stack_set(st.converged, idx, False),
        active=stack_set(st.active, idx, False),
        m_valid=stack_set(st.m_valid, idx, 0),
        n_valid=stack_set(st.n_valid, idx, 0),
        healthy=stack_set(st.healthy, idx, True)))


def cluster_done(cstate: ClusterLaneState, max_iters: int) -> jax.Array:
    """(D, L) bool: slot holds a finished problem (converged, capped, or
    frozen unhealthy — see ``ops.lane_done``)."""
    return ops.lane_done(cstate.lanes, max_iters)


@jax.jit
def cluster_poison_device(cstate: ClusterLaneState,
                          device) -> ClusterLaneState:
    """Corrupt device ``device``'s entire pool slice with NaN — the
    device-blackout fault model (an HBM/interconnect failure clobbering
    one shard's resident state, while the host-side request payloads stay
    intact). The chaos harness (``repro.serve.faults``) injects through
    this; the lane-health detector then flags every active lane of the
    device in its next chunk, which is the signature
    ``ClusterScheduler`` quarantines on. Inactive lanes' NaNs are inert:
    admission overwrites P/colsum/frow wholesale, so a blacked-out slot
    is clean again the moment it is refilled (tested)."""
    st = cstate.lanes
    nan = jnp.nan
    return ClusterLaneState(lanes=dataclasses.replace(
        st,
        P=stack_set(st.P, device, jnp.asarray(nan, st.P.dtype)),
        colsum=stack_set(st.colsum, device, nan),
        frow=stack_set(st.frow, device, nan)))


@functools.lru_cache(maxsize=None)
def _cluster_stepped_fn(mesh: Mesh, axis: str, n_iters: int, cfg: UOTConfig,
                        block_m, interpret, impl):
    """Compiled one-launch advance of a whole pool stack over ``mesh``.

    The shard_map body squeezes the per-device (1, L, ...) block to a plain
    single-device ``LaneState``, runs the ordinary stepped chunk on it, and
    restores the device dim. No collectives — check_vma is moot, but False
    matches the other shard_map solvers. Cached per (mesh, axis, chunk,
    cfg, flavor): building re-wraps shard_map + jit.
    """

    def advance_block(st: ops.LaneState) -> ops.LaneState:
        sq = jax.tree_util.tree_map(lambda x: jnp.squeeze(x, 0), st)
        out = ops.solve_fused_stepped(sq, n_iters, cfg, block_m=block_m,
                                      interpret=interpret, impl=impl)
        return jax.tree_util.tree_map(lambda x: x[None], out)

    sharded = jax.shard_map(advance_block, mesh=mesh, in_specs=(P(axis),),
                            out_specs=P(axis), check_vma=False)
    return jax.jit(sharded)


def cluster_stepped(cstate: ClusterLaneState, n_iters: int, cfg: UOTConfig,
                    *, mesh: Mesh | None = None, axis: str = "devices",
                    block_m: int | None = None,
                    interpret: bool | None = None,
                    impl: str | None = None) -> ClusterLaneState:
    """Advance every device's lane pool by up to ``n_iters`` iterations.

    With ``mesh``: ONE ``shard_map``-ped launch over ``axis`` — device d
    runs the standard stepped chunk on its own (L, Mp, Np) slice,
    collective-free. Without: a Python loop of D per-device launches whose
    shapes and programs are identical to the single-device scheduler's
    advance (the bit-identity oracle, and the fallback on 1-device hosts).

    ``impl`` semantics match ``ops.solve_fused_stepped`` ('auto' included);
    'auto' is resolved HERE, eagerly and once per call — by the pool's
    padded per-device shape, which is the same on every device — so the
    decision lands in ``ops.dispatch_stats`` once per cluster chunk and the
    compiled shard_map body is specialized to the resolved tier.
    ('kernel' inside shard_map is the TPU path; CPU meshes use 'jnp'.)
    """
    interp = ops._interpret_default(interpret)
    impl_r = ops._impl_default(impl, interp)
    if impl_r in ("auto", "resident"):
        Mp, Np = cstate.lanes.P.shape[2:]
        sdt = cstate.lanes.P.dtype
        if ops._resolve_auto(impl_r, Mp, Np, cfg, sdt, stepped_sdt=sdt):
            impl_r = "resident"
        else:
            impl_r = ops._impl_default(None, interp)
    if mesh is None:
        outs = [
            ops.solve_fused_stepped(cstate.device_state(d), n_iters, cfg,
                                    block_m=block_m, interpret=interpret,
                                    impl=impl_r)
            for d in range(cstate.num_devices)]
        return ClusterLaneState(lanes=jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *outs))
    if mesh.shape[axis] != cstate.num_devices:
        raise ValueError(f"pool stack has {cstate.num_devices} device "
                         f"slices but mesh axis {axis!r} has "
                         f"{mesh.shape[axis]} devices")
    fn = _cluster_stepped_fn(mesh, axis, n_iters, cfg, block_m, interpret,
                             impl_r)
    return ClusterLaneState(lanes=fn(cstate.lanes))
