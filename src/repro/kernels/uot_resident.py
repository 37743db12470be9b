"""VMEM-resident multi-iteration MAP-UOT Pallas kernels.

The streamed kernels (``uot_fused``, ``uot_batched``) hit the paper's
per-iteration HBM floor: read MN + write MN bytes per iteration, because the
grid walks row blocks and every iteration is its own ``pallas_call``. For the
bucketed serving shapes this repo targets (e.g. 256x384 fp32 = 384 KB) the
whole coupling matrix fits in VMEM — so the true floor is not ``2*MN`` per
*iteration* but ``MN in + MN out`` per *solve*: load the tile once, iterate
to convergence on-chip, store once.

These kernels realize that tier. The grid iterates over **lanes** (the batch
dimension) instead of row blocks; each grid step

  1. DMAs one problem's whole ``(Mp, Np)`` tile into VMEM and upcasts it to
     ``acc_dtype`` ONCE (for bf16 storage the per-iteration rounding of the
     streamed path disappears — the resident trajectory is the fp32
     trajectory, downcast once at the end),
  2. runs a ``lax.while_loop``/``fori_loop`` of full Algorithm-1 iterations
     (column rescale, row sums, row rescale, column-sum accumulation)
     entirely in VMEM, with the row-factor-stationarity convergence check
     (``max|frow_t - frow_{t-1}| <= tol``, exactly the streamed solvers'
     criterion) folded INTO the loop condition — a converged lane stops
     computing instead of being masked,
  3. writes the converged tile back once, downcasting to the storage dtype.

Per-solve HBM traffic collapses from ``iters * MN * (in+out)`` bytes to
``MN * (in+out)`` + O(M+N) — for a 25-iteration solve, 25x less. Grid steps
are sequential on the TensorCore, so per-lane while_loops of different trip
counts simply take different time; no cross-lane synchronization exists to
drag a fast lane to the slowest one's iteration count.

Four entry points (wrapped with padding/dispatch by ``ops``):

- ``resident_solve``: one-shot batched solve returning per-lane iteration
  counts and final drift alongside (P, colsum).
- ``resident_solve_pc``: the implicit-geometry twin — each lane's tile is
  COMPUTED in VMEM from point-cloud coordinates (``repro.geometry``
  tile arithmetic, bit-identical to the dense mirror) instead of DMA'd,
  so per-solve coupling traffic is ``write MN`` only and the VMEM budget
  shrinks to the coupling (``ops.resident_fits(implicit=True)``).
- ``resident_solve_jnp``: the pure-XLA mirror of the same iteration fusion
  (single jit, fp32 throughout, one downcast) so non-TPU backends get the
  fused-iteration win without interpret-mode overhead and CPU CI can
  measure it.
- ``resident_stepped``: the ``ops.LaneState``-compatible chunk advance —
  per-lane gating (active, not converged, below the iteration cap) is the
  while_loop condition, so ``UOTScheduler`` chunks become ONE launch with
  zero inter-iteration HBM round trips.

Whether a problem belongs here is a static VMEM-budget question answered by
``ops.resident_fits``; ``ops``' ``impl='auto'`` routes between this tier and
the streamed kernels per problem shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.geometry.pointcloud import gibbs_tile
from repro.kernels.uot_fused import _safe_pow
from repro.kernels.vmem import COMPILER_PARAMS


def _one_iteration(A, colsum, a, b, fi):
    """One full Algorithm-1 iteration on an in-VMEM (1, Mp, Np) tile.

    Returns (A', colsum', frow) — identical math to the streamed kernels'
    single pass (column rescale -> row sums -> row rescale -> column-sum
    accumulation), just with the tile already resident.
    """
    A = A * _safe_pow(b, colsum, fi)              # I:   column rescale
    rowsum = jnp.sum(A, axis=2, keepdims=True)    # II:  row sums
    frow = _safe_pow(a, rowsum, fi)
    A = A * frow                                  # III: row rescale
    colsum = jnp.sum(A, axis=1, keepdims=True)    # IV:  next column sums
    return A, colsum, frow


def _solve_to_convergence(A, a_ref, b_ref, *, fi: float, num_iters: int,
                          tol, acc_dtype):
    """The shared in-VMEM solve loop: Algorithm-1 iterations on an already
    loaded (or computed) ``acc_dtype`` tile, with the row-factor
    stationarity check folded into the loop condition.

    Returns (A, colsum, it, err). Both the dense-load kernel and the
    implicit-geometry kernel (``_resident_pc_kernel``) run exactly this
    loop — the tile source is the only difference between the tiers.
    """
    a = a_ref[...].astype(acc_dtype)              # (1, Mp, 1)
    b = b_ref[...].astype(acc_dtype)              # (1, 1, Np)
    colsum = jnp.sum(A, axis=1, keepdims=True)    # Algorithm-1 preprocessing
    prev = jnp.ones_like(a)
    err0 = jnp.asarray(jnp.inf, acc_dtype)

    if tol is None:
        def body(_, carry):
            A, colsum, prev, _ = carry
            A, colsum, frow = _one_iteration(A, colsum, a, b, fi)
            return A, colsum, frow, jnp.max(jnp.abs(frow - prev))
        A, colsum, prev, err = jax.lax.fori_loop(
            0, num_iters, body, (A, colsum, prev, err0))
        it = jnp.int32(num_iters)
    else:
        def cond(carry):
            _, _, _, it, err = carry
            return jnp.logical_and(it < num_iters, err > tol)

        def body(carry):
            A, colsum, prev, it, _ = carry
            A, colsum, frow = _one_iteration(A, colsum, a, b, fi)
            return A, colsum, frow, it + 1, jnp.max(jnp.abs(frow - prev))
        A, colsum, prev, it, err = jax.lax.while_loop(
            cond, body, (A, colsum, prev, jnp.int32(0), err0))
    return A, colsum, it, err


def _store_solution(A, colsum, it, err, out_ref, colsum_ref, iters_ref,
                    err_ref):
    out_ref[...] = A.astype(out_ref.dtype)        # downcast ONCE
    colsum_ref[...] = colsum.astype(colsum_ref.dtype)
    iters_ref[...] = jnp.full(iters_ref.shape, it, iters_ref.dtype)
    err_ref[...] = jnp.full(err_ref.shape, err, err_ref.dtype)


def _resident_solve_kernel(a_ref, b_ref, A_ref, out_ref, colsum_ref,
                           iters_ref, err_ref, *, fi: float, num_iters: int,
                           tol, acc_dtype):
    A = A_ref[...].astype(acc_dtype)              # upcast ONCE
    A, colsum, it, err = _solve_to_convergence(
        A, a_ref, b_ref, fi=fi, num_iters=num_iters, tol=tol,
        acc_dtype=acc_dtype)
    _store_solution(A, colsum, it, err, out_ref, colsum_ref, iters_ref,
                    err_ref)


@functools.partial(jax.jit, static_argnames=("fi", "num_iters", "tol",
                                             "interpret", "acc_dtype"))
def resident_solve(A: jax.Array, a: jax.Array, b: jax.Array, *, fi: float,
                   num_iters: int, tol: float | None = None,
                   interpret: bool = False, acc_dtype=jnp.float32):
    """Whole-solve resident kernel: a stack of problems, one launch, one
    HBM read + one write of each coupling for the ENTIRE solve.

    A: (B, Mp, Np) pre-padded (Mp % sublane == 0, Np % 128 == 0; zero
    rows/cols are exact no-ops); a: (B, Mp); b: (B, Np). The grid iterates
    over lanes; each lane runs up to ``num_iters`` Algorithm-1 iterations in
    VMEM, early-exiting when its row-factor stationarity reaches ``tol``
    (same criterion, same iterate, same count as the streamed solvers).

    Returns (A_out, colsum, iters, err): the converged couplings in the
    storage dtype of ``A``, their fp32 carried column sums, and per-lane
    iteration counts / final drifts.
    """
    B, M, N = A.shape
    kernel = functools.partial(_resident_solve_kernel, fi=fi,
                               num_iters=num_iters, tol=tol,
                               acc_dtype=acc_dtype)
    out, colsum, iters, err = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, M, 1), lambda i: (i, 0, 0)),   # a (RPD)
            pl.BlockSpec((1, 1, N), lambda i: (i, 0, 0)),   # b (CPD)
            pl.BlockSpec((1, M, N), lambda i: (i, 0, 0)),   # whole tile
        ],
        out_specs=[
            pl.BlockSpec((1, M, N), lambda i: (i, 0, 0)),   # converged tile
            pl.BlockSpec((1, 1, N), lambda i: (i, 0, 0)),   # colsum
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),   # iters
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),   # err
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, M, N), A.dtype),
            jax.ShapeDtypeStruct((B, 1, N), acc_dtype),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, 1), acc_dtype),
        ],
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(a.reshape(B, M, 1), b.reshape(B, 1, N), A)
    return out, colsum.reshape(B, N), iters.reshape(B), err.reshape(B)


def _resident_pc_kernel(a_ref, b_ref, x_ref, xn_ref, y_ref, yn_ref,
                        mv_ref, nv_ref, out_ref, colsum_ref, iters_ref,
                        err_ref, *, fi: float, reg: float, scale: float,
                        num_iters: int, tol, acc_dtype, barrier: bool):
    # the Gibbs tile never exists in HBM: computed here, in VMEM, from the
    # O((M + N) * d) coordinate operands, then iterated on like the loaded
    # tile of _resident_solve_kernel (same loop, bit-for-bit)
    A = gibbs_tile(x_ref[...], xn_ref[...], y_ref[...], yn_ref[...],
                   reg=reg, scale=scale, barrier=barrier)
    rows = jax.lax.broadcasted_iota(jnp.int32, A.shape, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, A.shape, 2)
    A = jnp.where((rows < mv_ref[0, 0, 0]) & (cols < nv_ref[0, 0, 0]), A, 0.0)
    if jnp.dtype(out_ref.dtype) != jnp.dtype(acc_dtype):
        # round through the storage dtype so the iterate matches what the
        # dense path reads back from an HBM tile stored in that dtype
        A = A.astype(out_ref.dtype)
    A = A.astype(acc_dtype)
    A, colsum, it, err = _solve_to_convergence(
        A, a_ref, b_ref, fi=fi, num_iters=num_iters, tol=tol,
        acc_dtype=acc_dtype)
    _store_solution(A, colsum, it, err, out_ref, colsum_ref, iters_ref,
                    err_ref)


@functools.partial(jax.jit, static_argnames=("fi", "reg", "scale",
                                             "num_iters", "tol", "interpret",
                                             "acc_dtype", "out_dtype"))
def resident_solve_pc(x, xn, y, yn, a, b, m_valid, n_valid, *, fi: float,
                      reg: float, scale: float = 1.0, num_iters: int,
                      tol: float | None = None, interpret: bool = False,
                      acc_dtype=jnp.float32, out_dtype=jnp.float32):
    """Whole-solve resident kernel for an implicit point-cloud geometry.

    Like ``resident_solve``, but each lane's tile is COMPUTED in VMEM from
    its coordinates (x: (B, Mp, d), xn: (B, Mp), y/yn likewise; m_valid /
    n_valid: (B,) valid counts masking the zero-padded region to exact
    zeros) instead of DMA'd from HBM. Per-solve coupling HBM traffic is
    therefore ``write MN`` — the dense resident tier's ``read MN`` input
    leg becomes an O((M + N) * d) coordinate read — and, because the input
    tile no longer occupies a VMEM slot, the budget test that gates this
    tier shrinks to the coupling alone (``ops.resident_fits`` with
    ``implicit=True``), admitting shapes the dense tier must stream.

    Returns (P, colsum, iters, err) exactly like ``resident_solve`` — same
    in-VMEM loop, same convergence criterion, same per-lane counts.
    """
    B, M, d = x.shape
    N = y.shape[1]
    kernel = functools.partial(_resident_pc_kernel, fi=fi, reg=reg,
                               scale=scale, num_iters=num_iters, tol=tol,
                               acc_dtype=acc_dtype, barrier=interpret)
    out, colsum, iters, err = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, M, 1), lambda i: (i, 0, 0)),   # a (RPD)
            pl.BlockSpec((1, 1, N), lambda i: (i, 0, 0)),   # b (CPD)
            pl.BlockSpec((1, M, d), lambda i: (i, 0, 0)),   # x coords
            pl.BlockSpec((1, M, 1), lambda i: (i, 0, 0)),   # x sq norms
            pl.BlockSpec((1, N, d), lambda i: (i, 0, 0)),   # y coords
            pl.BlockSpec((1, 1, N), lambda i: (i, 0, 0)),   # y sq norms
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),   # m_valid
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),   # n_valid
        ],
        out_specs=[
            pl.BlockSpec((1, M, N), lambda i: (i, 0, 0)),   # converged tile
            pl.BlockSpec((1, 1, N), lambda i: (i, 0, 0)),   # colsum
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),   # iters
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),   # err
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, M, N), out_dtype),
            jax.ShapeDtypeStruct((B, 1, N), acc_dtype),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, 1), acc_dtype),
        ],
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(a.reshape(B, M, 1), b.reshape(B, 1, N), x, xn.reshape(B, M, 1),
      y, yn.reshape(B, 1, N), m_valid.astype(jnp.int32).reshape(B, 1, 1),
      n_valid.astype(jnp.int32).reshape(B, 1, 1))
    return out, colsum.reshape(B, N), iters.reshape(B), err.reshape(B)


@functools.partial(jax.jit, static_argnames=("fi", "num_iters", "tol",
                                             "out_dtype"))
def resident_solve_jnp(A: jax.Array, a: jax.Array, b: jax.Array, *,
                       fi: float, num_iters: int, tol: float | None = None,
                       out_dtype=None):
    """Pure-XLA mirror of ``resident_solve``: the same iteration fusion
    (ONE jit, fp32 state throughout, no per-iteration storage round trip)
    vectorized over the batch.

    Where the streamed ``'jnp'`` path downcasts the coupling to the storage
    dtype every iteration (mirroring what the streamed kernel's HBM writes
    do), this path upcasts once and downcasts once — for bf16 storage the
    iterates are the fp32 trajectory rounded at the end, exactly like the
    resident kernel. Frozen lanes are masked out of updates via unit
    factors (a multiplicative no-op, bit-exact), since XLA has no per-lane
    early exit; iteration counts still match the kernel per lane.

    Returns (A_out, colsum, iters, err) like ``resident_solve``.
    """
    B = A.shape[0]
    out_dtype = A.dtype if out_dtype is None else out_dtype
    A = A.astype(jnp.float32)                     # upcast ONCE
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    colsum = A.sum(axis=1)
    prev = jnp.ones_like(a)
    err0 = jnp.full((B,), jnp.inf, jnp.float32)

    def one_iter(A, colsum, upd):
        fcol = _safe_pow(b, colsum, fi)
        if upd is not None:
            fcol = jnp.where(upd[:, None], fcol, 1.0)
        A = A * fcol[:, None, :]
        frow = _safe_pow(a, A.sum(axis=2), fi)
        frow_m = frow if upd is None else jnp.where(upd[:, None], frow, 1.0)
        A = A * frow_m[:, :, None]
        newcs = A.sum(axis=1)
        if upd is not None:
            newcs = jnp.where(upd[:, None], newcs, colsum)
        return A, newcs, frow

    if tol is None:
        def body(_, carry):
            A, colsum, prev, _ = carry
            A, colsum, frow = one_iter(A, colsum, None)
            return A, colsum, frow, jnp.max(jnp.abs(frow - prev), axis=-1)
        A, colsum, prev, err = jax.lax.fori_loop(
            0, num_iters, body, (A, colsum, prev, err0))
        iters = jnp.full((B,), num_iters, jnp.int32)
    else:
        def cond(carry):
            _, _, _, _, _, conv, i = carry
            return jnp.logical_and(i < num_iters, ~jnp.all(conv))

        def body(carry):
            A, colsum, prev, err, iters, conv, i = carry
            upd = ~conv
            A, colsum, frow = one_iter(A, colsum, upd)
            drift = jnp.max(jnp.abs(frow - prev), axis=-1)
            err = jnp.where(upd, drift, err)
            prev = jnp.where(upd[:, None], frow, prev)
            return (A, colsum, prev, err, iters + upd.astype(jnp.int32),
                    conv | (upd & (drift <= tol)), i + 1)

        A, colsum, prev, err, iters, conv, i = jax.lax.while_loop(
            cond, body, (A, colsum, prev, err0, jnp.zeros((B,), jnp.int32),
                         jnp.zeros((B,), bool), jnp.int32(0)))
    return A.astype(out_dtype), colsum, iters, err   # downcast ONCE


def _resident_stepped_kernel(active_ref, conv_ref, iters_ref, a_ref, b_ref,
                             cs_ref, frow_ref, A_ref, out_ref, cs_out_ref,
                             frow_out_ref, iters_out_ref, conv_out_ref, *,
                             fi: float, n_iters: int, num_iters: int, tol,
                             acc_dtype):
    A = A_ref[...].astype(acc_dtype)              # upcast ONCE per chunk
    a = a_ref[...].astype(acc_dtype)
    b = b_ref[...].astype(acc_dtype)
    colsum = cs_ref[...].astype(acc_dtype)        # carried, (1, 1, Np)
    prev = frow_ref[...].astype(acc_dtype)        # carried, (1, Mp, 1)
    live = jnp.logical_and(active_ref[0, 0, 0] > 0, conv_ref[0, 0, 0] == 0)
    conv0 = conv_ref[0, 0, 0] > 0
    it0 = iters_ref[0, 0, 0]

    # The streamed stepped path updates a lane iff it is active, not yet
    # converged, and below the iteration cap — here that gate IS the loop
    # condition, so a finished (or free) lane's tile round-trips bit-exact
    # with zero iterations of compute.
    def cond(carry):
        _, _, _, it, conv, k = carry
        run = jnp.logical_and(live, jnp.logical_not(conv))
        return jnp.logical_and(jnp.logical_and(k < n_iters, run),
                               it < num_iters)

    def body(carry):
        A, colsum, prev, it, conv, k = carry
        A, colsum, frow = _one_iteration(A, colsum, a, b, fi)
        if tol is not None:
            conv = jnp.logical_or(conv, jnp.max(jnp.abs(frow - prev)) <= tol)
        return A, colsum, frow, it + 1, conv, k + 1

    A, colsum, prev, it, conv, _ = jax.lax.while_loop(
        cond, body, (A, colsum, prev, it0, conv0, jnp.int32(0)))

    out_ref[...] = A.astype(out_ref.dtype)        # downcast ONCE per chunk
    cs_out_ref[...] = colsum.astype(cs_out_ref.dtype)
    frow_out_ref[...] = prev.astype(frow_out_ref.dtype)
    iters_out_ref[...] = jnp.full(iters_out_ref.shape, it,
                                  iters_out_ref.dtype)
    conv_out_ref[...] = jnp.full(conv_out_ref.shape,
                                 conv.astype(conv_out_ref.dtype))


@functools.partial(jax.jit, static_argnames=("fi", "n_iters", "num_iters",
                                             "tol", "interpret", "acc_dtype"))
def resident_stepped(A: jax.Array, colsum: jax.Array, frow: jax.Array,
                     iters: jax.Array, converged: jax.Array,
                     active: jax.Array, a: jax.Array, b: jax.Array, *,
                     fi: float, n_iters: int, num_iters: int,
                     tol: float | None = None, interpret: bool = False,
                     acc_dtype=jnp.float32):
    """Chunk advance for a lane pool with the whole chunk resident in VMEM.

    The kernel form of ``ops.solve_fused_stepped``'s loop body: one launch
    advances every live lane by up to ``n_iters`` Algorithm-1 iterations
    with the lane's tile loaded into VMEM once — the streamed stepped path
    pays read+write MN per iteration per lane, this pays it per CHUNK. The
    per-lane gating (active, not converged, ``iters < num_iters``) and the
    tol freeze are the while_loop condition, so a lane that converges
    mid-chunk stops at exactly the same iterate and count as the streamed
    path (asserted in tests/test_resident.py).

    For sub-fp32 storage the tile is rounded once per chunk, not once per
    iteration — a bf16 lane's trajectory therefore depends on chunk
    boundaries, which is why ``impl='auto'`` only routes fp32 pools here
    (see ``ops.solve_fused_stepped``).

    Arrays are the corresponding ``LaneState`` fields; ``converged`` and
    ``active`` may be bool (cast to the kernel's f32/i32 carriers here).
    Returns (P, colsum, frow, iters, converged-as-int32).
    """
    B, M, N = A.shape
    kernel = functools.partial(_resident_stepped_kernel, fi=fi,
                               n_iters=n_iters, num_iters=num_iters, tol=tol,
                               acc_dtype=acc_dtype)
    out, cs, fr, it, conv = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),   # active
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),   # converged
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),   # iters
            pl.BlockSpec((1, M, 1), lambda i: (i, 0, 0)),   # a
            pl.BlockSpec((1, 1, N), lambda i: (i, 0, 0)),   # b
            pl.BlockSpec((1, 1, N), lambda i: (i, 0, 0)),   # carried colsum
            pl.BlockSpec((1, M, 1), lambda i: (i, 0, 0)),   # carried frow
            pl.BlockSpec((1, M, N), lambda i: (i, 0, 0)),   # P tile
        ],
        out_specs=[
            pl.BlockSpec((1, M, N), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, N), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, M, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, M, N), A.dtype),
            jax.ShapeDtypeStruct((B, 1, N), acc_dtype),
            jax.ShapeDtypeStruct((B, M, 1), acc_dtype),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, 1), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(active.astype(jnp.float32).reshape(B, 1, 1),
      converged.astype(jnp.float32).reshape(B, 1, 1),
      iters.astype(jnp.int32).reshape(B, 1, 1),
      a.reshape(B, M, 1), b.reshape(B, 1, N),
      colsum.reshape(B, 1, N), frow.reshape(B, M, 1), A)
    return (out, cs.reshape(B, N), fr.reshape(B, M), it.reshape(B),
            conv.reshape(B))
