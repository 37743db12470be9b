"""Distributed UOT solvers — the paper's Tianhe-1 design in shard_map.

The paper scales MAP-UOT to the Tianhe-1 supercomputer by row-sharding the
coupling matrix across MPI ranks; the only communication per iteration is an
``MPI_Allreduce`` of the length-N partial column sums (Algorithm 1 lines
16-20 replaced by the allreduce). We map this 1:1 onto JAX:

  rank                -> mesh device along a named axis
  row-shard of A      -> shard_map block of A sharded on that axis
  MPI_Allreduce       -> jax.lax.psum of the local column-sum partials

The row-sharded gang (``rowsharded_fused_solver``) is not a loop of its
own: each device runs the streamed kernel tier's solve loop,
``kernels.ops.streamed_solve``, on its row block, with the psum where one
device sums alone and the ``cfg.tol`` drift taken over the mesh, so it
stops on the same iteration with the same iterate as the one-device
solve. ``gang_solve_sharded`` runs it on inputs already on the mesh and
returns the iteration count.

Beyond the paper we add:
  * a 2-D sharded solver (rows on one axis, columns on another) for matrices
    too large for 1-D sharding — row sums psum over the column axis and
    column sums psum over the row axis;
  * an overlapped variant that hides the column-sum reduction behind the
    next row-block's compute using a ppermute ring (compute/comm overlap);
  * optional bf16 storage with fp32 reduction (``storage_dtype=`` on every
    solver builder): each row block lives in the storage dtype between
    iterations, is upcast once per iteration for the rescale math, and
    every sum / psum / ppermute reduction accumulates fp32 — halving the
    resident bytes per device while the collectives stay fp32-exact;
  * ``gang_solve`` — the serving-tier entry adapter: pad rows to the mesh
    size, shard, run the row-sharded gang, hand back trimmed host numpy.
    ``repro.cluster.ClusterScheduler`` routes problems too large for any
    lane pool here instead of rejecting them.

All variants produce iterates identical to ``sinkhorn_uot_fused`` (up to
float reduction order; bf16 storage to the documented bf16 bars) —
asserted in tests on forced host devices. The 2-D and overlapped variants
run the fixed ``cfg.num_iters`` in plain XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.problem import UOTConfig, rescale_factors
from repro.kernels import ops


def _storage(cfg: UOTConfig, storage_dtype) -> jnp.dtype:
    return jnp.dtype(storage_dtype if storage_dtype is not None
                     else cfg.dtype)


# ---------------------------------------------------------------------------
# 1-D row-sharded MAP-UOT (the paper's cluster design)
# ---------------------------------------------------------------------------

GANG_SPAN = "gang.solve"


def rowsharded_fused_solver(mesh: Mesh, axis: str, cfg: UOTConfig, *,
                            storage_dtype=None, impl: str | None = None):
    """Build the jitted row-sharded gang solve over ``mesh``'s ``axis``.

    Returns ``solve(K, a, b) -> (A, colsum, iters)``. K is sharded
    ``P(axis, None)``, a ``P(axis)`` and b replicated; A comes back
    sharded like K, colsum and the iteration count replicated.

    Each device runs the streamed tier's solve loop
    (``ops.streamed_solve``) on its row block, with the MAP-UOT kernels
    where ``ops.solve_fused`` would run them: one psum (==
    MPI_Allreduce) of the fp32 column-sum partials per iteration, and
    with ``cfg.tol`` the same stopping rule and iterate as the one-device
    solve, the drift taken over the whole mesh. The loop writes each
    device's coupling in place; the caller's K is read and not copied.

    ``storage_dtype`` (default ``cfg.dtype``) is the dtype each device
    keeps its row block in between iterations; the rescale math and every
    reduction (local sums and the psum) run fp32. ``impl`` as in
    ``ops.solve_fused_batched`` ('kernel' on TPU by default, 'jnp'
    elsewhere).
    """
    def solve_shard(K_blk, a_blk, b):
        A, colsum, iters = ops.streamed_solve(
            K_blk[None], a_blk[None], b[None], cfg,
            storage_dtype=storage_dtype, impl=impl, axis=axis)
        return A[0], colsum[0], iters

    sharded = jax.shard_map(
        solve_shard, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P()),
        out_specs=(P(axis, None), P(), P()),
        check_vma=False)
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# 2-D sharded MAP-UOT (beyond paper: rows x cols over two mesh axes)
# ---------------------------------------------------------------------------

def sharded2d_fused_solver(mesh: Mesh, row_axis: str, col_axis: str,
                           cfg: UOTConfig, *, storage_dtype=None):
    """2-D sharded solver: A sharded P(row_axis, col_axis).

    Row sums need a psum over ``col_axis``; column sums a psum over
    ``row_axis``. Marginals a sharded on row_axis, b on col_axis. Two small
    vector collectives per iteration — still O(M/Pr + N/Pc) bytes, never the
    matrix itself. ``storage_dtype`` as in ``rowsharded_fused_solver``:
    blocks stored in it, all math and both psums fp32.
    """
    fi = cfg.fi
    sdt = _storage(cfg, storage_dtype)

    def solve_shard(A_blk, a_blk, b_blk):
        A_blk = A_blk.astype(sdt)
        colsum = jax.lax.psum(A_blk.astype(jnp.float32).sum(axis=0),
                              row_axis)

        def body(_, carry):
            A_blk, colsum = carry
            blk = A_blk.astype(jnp.float32)
            blk = blk * rescale_factors(b_blk, colsum, fi)[None, :]
            rowsum = jax.lax.psum(blk.sum(axis=1), col_axis)
            blk = blk * rescale_factors(a_blk, rowsum, fi)[:, None]
            colsum = jax.lax.psum(blk.sum(axis=0), row_axis)
            return blk.astype(sdt), colsum

        A_blk, colsum = jax.lax.fori_loop(
            0, cfg.num_iters, body, (A_blk, colsum))
        return A_blk, colsum

    sharded = jax.shard_map(
        solve_shard, mesh=mesh,
        in_specs=(P(row_axis, col_axis), P(row_axis), P(col_axis)),
        out_specs=(P(row_axis, col_axis), P(col_axis)),
        check_vma=False)
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# Overlapped variant: ring-reduce column partials behind next block compute
# ---------------------------------------------------------------------------

def rowsharded_overlapped_solver(mesh: Mesh, axis: str, cfg: UOTConfig,
                                 num_chunks: int = 4, *,
                                 storage_dtype=None):
    """Row-sharded solver that overlaps the column-sum reduction with compute.

    The local row block is split into ``num_chunks`` chunks. After chunk k's
    partial column sums are ready, a ring reduce-scatter step (ppermute) for
    chunk k-1's partials runs concurrently with chunk k+1's compute — XLA's
    async collective scheduling on TPU overlaps the ppermute DMA with the VPU
    work. The final factors equal the blocking psum version exactly.

    This mirrors (and improves on) the paper's blocking MPI_Allreduce: on
    Tianhe-1 the allreduce serializes after the pass; here it rides along.
    ``storage_dtype`` as in ``rowsharded_fused_solver``: chunks are upcast
    to fp32 for the rescale math and the ring partials stay fp32.
    """
    fi = cfg.fi
    n_dev = mesh.shape[axis]
    sdt = _storage(cfg, storage_dtype)

    def solve_shard(A_blk, a_blk, b):
        A_blk = A_blk.astype(sdt)
        Mloc = A_blk.shape[0]
        chunk = Mloc // num_chunks

        def one_iter(carry, _):
            A_blk, colsum = carry
            fcol = rescale_factors(b, colsum, fi)

            def chunk_body(k, state):
                A_blk, acc = state
                blk = jax.lax.dynamic_slice_in_dim(A_blk, k * chunk, chunk, 0)
                blk = blk.astype(jnp.float32) * fcol[None, :]
                rowsum = blk.sum(axis=1)
                a_chunk = jax.lax.dynamic_slice_in_dim(a_blk, k * chunk, chunk, 0)
                blk = blk * rescale_factors(a_chunk, rowsum, fi)[:, None]
                acc = acc + blk.sum(axis=0)
                A_blk = jax.lax.dynamic_update_slice_in_dim(
                    A_blk, blk.astype(sdt), k * chunk, 0)
                return A_blk, acc

            A_blk, partial = jax.lax.fori_loop(
                0, num_chunks, chunk_body,
                (A_blk, jnp.zeros_like(colsum)))
            # Ring all-reduce of partials via ppermute (log-free, n-1 steps);
            # on TPU each step is an async DMA that overlaps with the next
            # iteration's first chunks once XLA's LHS kicks in.
            acc = partial
            perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
            recv = partial
            for _ in range(n_dev - 1):
                recv = jax.lax.ppermute(recv, axis, perm)
                acc = acc + recv
            return (A_blk, acc), None

        colsum0 = jax.lax.psum(A_blk.astype(jnp.float32).sum(axis=0), axis)
        (A_blk, colsum), _ = jax.lax.scan(
            one_iter, (A_blk, colsum0), None, length=cfg.num_iters)
        return A_blk, colsum

    sharded = jax.shard_map(
        solve_shard, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P()),
        out_specs=(P(axis, None), P()),
        check_vma=False)
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# Helpers
# ---------------------------------------------------------------------------

def shard_inputs(mesh: Mesh, axis: str, A, a, b):
    """Place (A, a, b) with the 1-D row sharding used by the solvers."""
    sA = jax.device_put(A, NamedSharding(mesh, P(axis, None)))
    sa = jax.device_put(a, NamedSharding(mesh, P(axis)))
    sb = jax.device_put(b, NamedSharding(mesh, P()))
    return sA, sa, sb


# ---------------------------------------------------------------------------
# Gang entries: device-resident inputs, and the serving tier's host adapter
# ---------------------------------------------------------------------------

# Built solver fns per (mesh, axis, cfg, storage dtype, num_chunks-or-None):
# building re-traces shard_map + jit, so serving traffic must reuse them.
_GANG_SOLVERS: dict = {}


def _gang_solver(mesh: Mesh, axis: str, cfg: UOTConfig, storage_dtype,
                 num_chunks: int | None = None):
    sdt = _storage(cfg, storage_dtype)
    key = (mesh, axis, cfg, sdt.name, num_chunks)
    solver = _GANG_SOLVERS.get(key)
    if solver is None:
        solver = _GANG_SOLVERS[key] = (
            rowsharded_fused_solver(mesh, axis, cfg,
                                    storage_dtype=storage_dtype)
            if num_chunks is None
            else rowsharded_overlapped_solver(mesh, axis, cfg,
                                              num_chunks=num_chunks,
                                              storage_dtype=storage_dtype))
    return solver


def gang_solve_sharded(mesh: Mesh, axis: str, K, a, b, cfg: UOTConfig, *,
                       storage_dtype=None, obs=None):
    """Solve one problem whose inputs are already on the mesh.

    K (M, N) sharded ``P(axis, None)``, a ``P(axis)``, b replicated, with
    M a multiple of the axis size (``shard_inputs`` places host arrays
    so). Runs ``rowsharded_fused_solver``, built once per (mesh, axis,
    cfg, storage dtype), and waits for it. Returns ``(A, colsum, iters)``
    with A left sharded like K and ``iters`` a Python int.

    ``obs`` (default: the process-global ``repro.obs`` bundle) gets a
    ``gang.solve`` phase over the launch and the wait, which is a
    ``TraceAnnotation`` on the device trace's clock, and two counters:
    ``gang.iters``, the iterations run, and ``gang.allreduce_bytes``, the
    per-device all-reduce bytes ``obs.traffic.gang_collective_bytes``
    charges for them.
    """
    from repro.obs import gang_collective_bytes, get_global

    obs = get_global() if obs is None else obs
    solver = _gang_solver(mesh, axis, cfg, storage_dtype)
    with obs.phases.phase(GANG_SPAN):
        A, colsum, iters = solver(K, a, b)
        iters = int(iters)
    obs.registry.counter("gang.iters").inc(iters)
    obs.registry.counter("gang.allreduce_bytes").inc(
        gang_collective_bytes(K.shape[1], iters))
    return A, colsum, iters


def gang_solve(mesh: Mesh, axis: str, K, a, b, cfg: UOTConfig, *,
               storage_dtype=None, overlapped: bool = False,
               num_chunks: int = 4, obs=None):
    """Solve one over-sized request on the row-sharded device gang.

    The serving-tier entry adapter that unifies the lane-pool and
    distributed tiers behind one submit API: ``repro.cluster``'s router
    sends problems whose shape fails the lane-pool budget here instead of
    rejecting them. Handles the impedance mismatch a raw request carries:

      * rows are zero-padded so M divides the gang size (zero rows have
        zero marginal mass -> unit factors -> stay zero: exact no-ops,
        the same invariant the lane pools rest on);
      * inputs are placed with ``shard_inputs`` (one host->device scatter
        of O(M*N/D) bytes per device) and solved by
        ``gang_solve_sharded`` (``obs`` as there);
      * the result is trimmed back to (M, N) host numpy.

    Honours ``cfg.tol`` as the one-device solve does. Returns
    ``(P, colsum)`` numpy arrays. ``overlapped=True`` uses the
    ring-reduce compute/comm-overlap variant, which runs the fixed
    ``cfg.num_iters``.
    """
    K = np.asarray(K)
    M, N = K.shape
    n_dev = mesh.shape[axis]
    # the overlapped solver's chunk loop covers Mloc // num_chunks * num_chunks
    # local rows, so rows must also divide into whole chunks per device —
    # otherwise tail rows are never rescaled and silently corrupt the
    # ring-reduced column sums
    row_mult = n_dev * num_chunks if overlapped else n_dev
    pm = (-M) % row_mult
    if pm:
        K = np.pad(K, ((0, pm), (0, 0)))
        a = np.pad(np.asarray(a), (0, pm))
    sdt = _storage(cfg, storage_dtype)
    sA, sa, sb = shard_inputs(mesh, axis, jnp.asarray(K, sdt),
                              jnp.asarray(a, jnp.float32),
                              jnp.asarray(b, jnp.float32))
    if overlapped:
        A, colsum = _gang_solver(mesh, axis, cfg, storage_dtype,
                                 num_chunks)(sA, sa, sb)
    else:
        A, colsum, _ = gang_solve_sharded(mesh, axis, sA, sa, sb, cfg,
                                          storage_dtype=storage_dtype,
                                          obs=obs)
    return np.asarray(A)[:M], np.asarray(colsum)
