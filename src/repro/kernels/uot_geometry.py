"""Implicit-geometry Pallas kernels: Gibbs tiles computed on-chip.

The streamed kernels (``uot_fused`` / ``uot_batched``) and the resident
tier (``uot_resident``) historically start from a dense initial coupling
``A0 = K`` in HBM — an ``M*N`` operand that had to be materialized
somewhere (host or device) before the solve. For implicit geometries
(``repro.geometry.PointCloudGeometry``) the Gibbs kernel is a function of
``O((M + N) * d)`` coordinates, so these kernels compute each ``(bm, N)``
tile of ``K = exp(-||x_i - y_j||^2 / (scale * reg))`` *in VMEM* from the
coordinate blocks instead of loading it:

- ``batched_pc_materialize`` — tile-compute -> store (the geometry path's
  answer to "give me A0 in HBM" when a downstream consumer needs it, e.g.
  admission into a scheduler lane pool).
- ``batched_pc_colsum`` — Algorithm 1's preprocessing pass with zero HBM
  coupling traffic: tiles are computed, column sums accumulated, nothing
  ``M*N``-sized is read **or written**.
- ``batched_pc_first_iteration`` — iteration 1 of Algorithm 1 with the
  input tile computed on-chip: the solve's first coupling write is the
  *rescaled* ``A1``, so the initial ``K`` never exists in HBM. Also emits
  the row factors (cheap O(M) write) so the tol machinery can track
  stationarity from iteration 1, exactly like the dense stepped kernel.

From iteration 2 on the coupling is the evolving solver state and the
standard streamed kernels take over — the geometry's job (sourcing the
cost) is done. Per-solve HBM coupling traffic therefore drops from
``materialize MN + read MN (colsum) + (read+write) MN * T`` to
``write MN + (read+write) MN * (T - 1)``, and nothing cost-shaped is ever
resident in HBM. The resident-tier twin (whole solve on-chip, store once)
is ``uot_resident.resident_solve_pc``.

Bitwise parity with the dense-load path (asserted in tests): the tile
arithmetic is ``repro.geometry.pointcloud.gibbs_tile`` — the same
unrolled, blocking-invariant chain the materializing mirror uses — and
each computed tile is routed through a storage-dtype roundtrip
(``astype(storage).astype(acc)``) so the iterate matches what the dense
path reads back from an HBM tile stored in that dtype. Zero-padding of a
dense stack becomes an in-kernel validity mask here (rows/cols at or past
a problem's ``(m_valid, n_valid)`` evaluate to exactly 0.0 — coordinates
always produce *nonzero* Gibbs entries, so unmasked padding would leak
mass into valid rows' sums).

Alignment contract matches ``uot_batched``: Mp % block_m == 0,
Np % 128 == 0 (ops pre-pads; padded coordinate rows are masked). The
coordinate blocks' minor dim is ``d`` (2-8), which interpret mode and the
VPU handle as-is; a hardware-TPU tuning pass may want coordinates laid
out lane-padded — a ROADMAP follow-on, not a semantics question.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.geometry.pointcloud import gibbs_tile
from repro.kernels.uot_fused import _safe_pow
from repro.kernels.vmem import COMPILER_PARAMS


def _tile(x_ref, xn_ref, y_ref, yn_ref, mv_ref, nv_ref, i, *, block_m: int,
          reg: float, scale: float, storage_dtype, acc_dtype, barrier: bool):
    """The shared tile prologue: compute, mask, storage-roundtrip.

    Returns the (1, bm, N) Gibbs tile in ``acc_dtype``, bit-identical to
    what the dense path would have loaded from an HBM copy of the
    zero-padded ``geometry.kernel(reg).astype(storage_dtype)``.
    """
    K = gibbs_tile(x_ref[...], xn_ref[...], y_ref[...], yn_ref[...],
                   reg=reg, scale=scale, barrier=barrier)
    shape = K.shape                                   # (1, bm, N)
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + i * block_m
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    K = jnp.where((rows < mv_ref[0, 0, 0]) & (cols < nv_ref[0, 0, 0]), K, 0.0)
    if jnp.dtype(storage_dtype) != jnp.dtype(acc_dtype):
        K = K.astype(storage_dtype)
    return K.astype(acc_dtype)


def _pc_specs(B, M, N, d, block_m):
    """in_specs for the (x, xn, y, yn, m_valid, n_valid) operand prefix."""
    return [
        pl.BlockSpec((1, block_m, d), lambda b, i: (b, i, 0)),  # x rows
        pl.BlockSpec((1, block_m, 1), lambda b, i: (b, i, 0)),  # x sq norms
        pl.BlockSpec((1, N, d), lambda b, i: (b, 0, 0)),        # y (whole)
        pl.BlockSpec((1, 1, N), lambda b, i: (b, 0, 0)),        # y sq norms
        pl.BlockSpec((1, 1, 1), lambda b, i: (b, 0, 0)),        # m_valid
        pl.BlockSpec((1, 1, 1), lambda b, i: (b, 0, 0)),        # n_valid
    ]


def _pc_args(x, xn, y, yn, m_valid, n_valid):
    B, M, d = x.shape
    N = y.shape[1]
    return (x, xn.reshape(B, M, 1), y, yn.reshape(B, 1, N),
            m_valid.astype(jnp.int32).reshape(B, 1, 1),
            n_valid.astype(jnp.int32).reshape(B, 1, 1))


def _materialize_kernel(x_ref, xn_ref, y_ref, yn_ref, mv_ref, nv_ref,
                        out_ref, *, block_m, reg, scale, acc_dtype, barrier):
    i = pl.program_id(1)
    K = _tile(x_ref, xn_ref, y_ref, yn_ref, mv_ref, nv_ref, i,
              block_m=block_m, reg=reg, scale=scale,
              storage_dtype=out_ref.dtype, acc_dtype=acc_dtype,
              barrier=barrier)
    out_ref[...] = K.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("reg", "scale", "block_m",
                                             "interpret", "acc_dtype",
                                             "out_dtype"))
def batched_pc_materialize(x, xn, y, yn, m_valid, n_valid, *, reg: float,
                           scale: float = 1.0, block_m: int = 256,
                           interpret: bool = False, acc_dtype=jnp.float32,
                           out_dtype=jnp.float32):
    """Materialize the zero-padded Gibbs stack from coordinates on-device.

    x: (B, Mp, d); xn: (B, Mp); y: (B, Np, d); yn: (B, Np); m_valid /
    n_valid: (B,) per-problem valid counts. Returns (B, Mp, Np) in
    ``out_dtype``. One tile-compute -> store pass: the cost matrix never
    exists, and the host never ships anything ``M*N``-sized.
    """
    B, M, d = x.shape
    N = y.shape[1]
    assert M % block_m == 0, (M, block_m)
    kernel = functools.partial(_materialize_kernel, block_m=block_m,
                               reg=reg, scale=scale, acc_dtype=acc_dtype,
                               barrier=interpret)
    return pl.pallas_call(
        kernel,
        grid=(B, M // block_m),
        in_specs=_pc_specs(B, M, N, d, block_m),
        out_specs=pl.BlockSpec((1, block_m, N), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, M, N), out_dtype),
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(*_pc_args(x, xn, y, yn, m_valid, n_valid))


def _colsum_kernel(x_ref, xn_ref, y_ref, yn_ref, mv_ref, nv_ref, cs_ref, *,
                   block_m, reg, scale, storage_dtype, acc_dtype, barrier):
    i = pl.program_id(1)
    K = _tile(x_ref, xn_ref, y_ref, yn_ref, mv_ref, nv_ref, i,
              block_m=block_m, reg=reg, scale=scale,
              storage_dtype=storage_dtype, acc_dtype=acc_dtype,
              barrier=barrier)

    @pl.when(i == 0)
    def _init():
        cs_ref[...] = jnp.zeros_like(cs_ref)

    cs_ref[...] += jnp.sum(K, axis=1, keepdims=True).astype(cs_ref.dtype)


@functools.partial(jax.jit, static_argnames=("reg", "scale", "block_m",
                                             "interpret", "storage_dtype",
                                             "acc_dtype"))
def batched_pc_colsum(x, xn, y, yn, m_valid, n_valid, *, reg: float,
                      scale: float = 1.0, block_m: int = 256,
                      interpret: bool = False, storage_dtype=jnp.float32,
                      acc_dtype=jnp.float32):
    """Initial column sums straight from coordinates: (B, Np) in acc_dtype.

    The Algorithm-1 preprocessing pass with ZERO M*N HBM traffic — the
    tiles live only in VMEM. ``storage_dtype`` is the dtype the dense path
    would have stored ``A0`` in; the computed tile takes the same rounding
    roundtrip so the sums match that path bit-for-bit.
    """
    B, M, d = x.shape
    N = y.shape[1]
    assert M % block_m == 0, (M, block_m)
    kernel = functools.partial(_colsum_kernel, block_m=block_m, reg=reg,
                               scale=scale, storage_dtype=storage_dtype,
                               acc_dtype=acc_dtype, barrier=interpret)
    out = pl.pallas_call(
        kernel,
        grid=(B, M // block_m),
        in_specs=_pc_specs(B, M, N, d, block_m),
        out_specs=pl.BlockSpec((1, 1, N), lambda b, i: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, N), acc_dtype),
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(*_pc_args(x, xn, y, yn, m_valid, n_valid))
    return out.reshape(B, N)


def _first_iter_kernel(fcol_ref, a_ref, x_ref, xn_ref, y_ref, yn_ref,
                       mv_ref, nv_ref, out_ref, colsum_ref, frow_ref, *,
                       fi, block_m, reg, scale, acc_dtype, barrier):
    i = pl.program_id(1)
    blk = _tile(x_ref, xn_ref, y_ref, yn_ref, mv_ref, nv_ref, i,
                block_m=block_m, reg=reg, scale=scale,
                storage_dtype=out_ref.dtype, acc_dtype=acc_dtype,
                barrier=barrier)

    # identical post-tile chain to uot_batched's fused iteration kernels —
    # the tile source is the only difference between the two paths
    blk = blk * fcol_ref[...].astype(acc_dtype)      # I: column rescale
    rowsum = jnp.sum(blk, axis=2, keepdims=True)     # II
    frow = _safe_pow(a_ref[...].astype(acc_dtype), rowsum, fi)
    blk = blk * frow                                 # III: row rescale

    out_ref[...] = blk.astype(out_ref.dtype)
    frow_ref[...] = frow.astype(frow_ref.dtype)

    @pl.when(i == 0)
    def _init():
        colsum_ref[...] = jnp.zeros_like(colsum_ref)

    colsum_ref[...] += jnp.sum(blk, axis=1,
                               keepdims=True).astype(colsum_ref.dtype)


@functools.partial(jax.jit, static_argnames=("fi", "reg", "scale", "block_m",
                                             "interpret", "acc_dtype",
                                             "out_dtype"))
def batched_pc_first_iteration(factor_col, a, x, xn, y, yn, m_valid,
                               n_valid, *, fi: float, reg: float,
                               scale: float = 1.0, block_m: int = 256,
                               interpret: bool = False,
                               acc_dtype=jnp.float32,
                               out_dtype=jnp.float32):
    """Iteration 1 of Algorithm 1 with the input tile computed on-chip.

    factor_col: (B, Np) column factors from ``batched_pc_colsum``'s sums;
    a: (B, Mp) row marginals; coordinate operands as in
    ``batched_pc_colsum``. Returns (A1, next_colsum, frow) of shapes
    (B, Mp, Np) [``out_dtype`` — the solve's storage dtype], (B, Np) and
    (B, Mp) [both acc]. The solve's first M*N HBM *write* is the already
    rescaled ``A1``; the Gibbs kernel itself never touches HBM. From here
    the standard streamed kernels iterate on ``A1``.
    """
    B, M, d = x.shape
    N = y.shape[1]
    assert M % block_m == 0, (M, block_m)
    kernel = functools.partial(_first_iter_kernel, fi=fi, block_m=block_m,
                               reg=reg, scale=scale, acc_dtype=acc_dtype,
                               barrier=interpret)
    out, colsum, frow = pl.pallas_call(
        kernel,
        grid=(B, M // block_m),
        in_specs=[
            pl.BlockSpec((1, 1, N), lambda b, i: (b, 0, 0)),        # fcol
            pl.BlockSpec((1, block_m, 1), lambda b, i: (b, i, 0)),  # a (RPD)
        ] + _pc_specs(B, M, N, d, block_m),
        out_specs=[
            pl.BlockSpec((1, block_m, N), lambda b, i: (b, i, 0)),  # A1 tile
            pl.BlockSpec((1, 1, N), lambda b, i: (b, 0, 0)),        # colsum
            pl.BlockSpec((1, block_m, 1), lambda b, i: (b, i, 0)),  # frow
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, M, N), out_dtype),
            jax.ShapeDtypeStruct((B, 1, N), acc_dtype),
            jax.ShapeDtypeStruct((B, M, 1), acc_dtype),
        ],
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(factor_col.reshape(B, 1, N), a.reshape(B, M, 1),
      *_pc_args(x, xn, y, yn, m_valid, n_valid))
    return out, colsum.reshape(B, N), frow.reshape(B, M)
