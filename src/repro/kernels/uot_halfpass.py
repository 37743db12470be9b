"""Half-fused MAP-UOT passes with 2-D (row x col) tiling for wide matrices.

When a full (block_m, N) stripe no longer fits VMEM (N beyond ~1M fp32
columns) the paper's GPU design applies: split the iteration into two
half-fused kernels, each one read+write pass (paper Algorithms 2 and 4):

  * ``scale_rows_accum_cols``  — A *= frow[:, None]; colsum += A.sum(0)
    (paper part 2). Grid is (col_blocks, row_blocks) with the ROW dimension
    innermost so each (1, bn) column-sum accumulator block sees all its
    contributing grid steps consecutively (TPU revisit rule) — this replaces
    the GPU's atomicAdd into global Sum_col.
  * ``scale_cols_accum_rows``  — A *= fcol[None, :]; rowsum += A.sum(1)
    (paper part 4). Grid is (row_blocks, col_blocks), column dim innermost.

Full iteration = both kernels = 2 reads + 2 writes (Q = 4MN elements), vs
6MN for the baseline, matching the paper's GPU traffic model. These kernels
are also the local building blocks of the 2-D sharded distributed solver.

Mixed precision: like ``uot_fused``, ``A`` may be stored bf16 — tiles are
upcast to ``acc_dtype`` (fp32) for the multiply and both reductions, and
downcast once on store, halving Q in bytes (``ops.solve_halfpass`` threads
``storage_dtype`` through both passes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.vmem import COMPILER_PARAMS


def _scale_rows_accum_cols_kernel(frow_ref, A_ref, out_ref, colsum_ref, *,
                                  acc_dtype):
    i = pl.program_id(1)  # row block (innermost)

    blk = A_ref[...].astype(acc_dtype) * frow_ref[...].astype(acc_dtype)
    out_ref[...] = blk.astype(out_ref.dtype)

    @pl.when(i == 0)
    def _init():
        colsum_ref[...] = jnp.zeros_like(colsum_ref)

    colsum_ref[...] += jnp.sum(blk, axis=0, keepdims=True).astype(colsum_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret",
                                             "acc_dtype"))
def scale_rows_accum_cols(A: jax.Array, frow: jax.Array, *, block_m: int = 256,
                          block_n: int = 512, interpret: bool = False,
                          acc_dtype=jnp.float32):
    """A * frow[:, None], plus column sums of the result. (paper part 2)."""
    M, N = A.shape
    assert M % block_m == 0 and N % block_n == 0, (A.shape, block_m, block_n)
    grid = (N // block_n, M // block_m)  # row dim innermost
    out, colsum = pl.pallas_call(
        functools.partial(_scale_rows_accum_cols_kernel, acc_dtype=acc_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, 1), lambda j, i: (i, 0)),       # frow
            pl.BlockSpec((block_m, block_n), lambda j, i: (i, j)),  # A
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda j, i: (i, j)),
            pl.BlockSpec((1, block_n), lambda j, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), A.dtype),
            jax.ShapeDtypeStruct((1, N), acc_dtype),
        ],
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(frow.reshape(M, 1), A)
    return out, colsum.reshape(N)


def _scale_cols_accum_rows_kernel(fcol_ref, A_ref, out_ref, rowsum_ref, *,
                                  acc_dtype):
    j = pl.program_id(1)  # col block (innermost)

    blk = A_ref[...].astype(acc_dtype) * fcol_ref[...].astype(acc_dtype)
    out_ref[...] = blk.astype(out_ref.dtype)

    @pl.when(j == 0)
    def _init():
        rowsum_ref[...] = jnp.zeros_like(rowsum_ref)

    rowsum_ref[...] += jnp.sum(blk, axis=1, keepdims=True).astype(rowsum_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret",
                                             "acc_dtype"))
def scale_cols_accum_rows(A: jax.Array, fcol: jax.Array, *, block_m: int = 256,
                          block_n: int = 512, interpret: bool = False,
                          acc_dtype=jnp.float32):
    """A * fcol[None, :], plus row sums of the result. (paper part 4)."""
    M, N = A.shape
    assert M % block_m == 0 and N % block_n == 0, (A.shape, block_m, block_n)
    grid = (M // block_m, N // block_n)  # col dim innermost
    out, rowsum = pl.pallas_call(
        functools.partial(_scale_cols_accum_rows_kernel, acc_dtype=acc_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),        # fcol
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),  # A
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), A.dtype),
            jax.ShapeDtypeStruct((M, 1), acc_dtype),
        ],
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(fcol.reshape(1, N), A)
    return out, rowsum.reshape(M)
