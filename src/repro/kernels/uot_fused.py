"""MAP-UOT fused-iteration Pallas TPU kernel.

The paper's single-pass interweaving (Algorithm 1) mapped to the TPU memory
hierarchy. One pallas_call performs a FULL UOT iteration (column rescale +
row rescale + next-iteration column-sum accumulation) streaming the coupling
matrix HBM -> VMEM -> HBM exactly once:

    grid step i (sequential on the TensorCore):
        blk  = A[i*bm:(i+1)*bm, :]          # (bm, N) tile, DMA'd to VMEM
        blk *= factor_col[None, :]          # computation I   (col rescale)
        rowsum = blk.sum(1)                 # computation II  (VPU reduce)
        blk *= ((a_i / rowsum) ** fi)[:,N]  # computation III (row rescale)
        colsum_acc += blk.sum(0)            # computation IV  (VMEM acc)
        A[i*bm:(i+1)*bm, :] = blk           # written back once

TPU adaptation notes (DESIGN.md section 2): the paper's per-thread
``NextSum_col[T][N]`` partials + pthread join become a single VMEM
accumulator revisited across *sequential* grid steps (no atomics needed);
AVX2 vectorization becomes (8, 128)-aligned VPU tiles; the GPU warp-shuffle
reduction degenerates to a VPU cross-lane ``jnp.sum``.

HBM traffic per iteration: read MN + write MN elements (+O(M+N)) — the
information-theoretic minimum — vs 4 reads + 2 writes for the POT baseline.

Mixed precision: ``A`` may be stored bf16 (the tile is upcast to
``acc_dtype`` fp32 on load, all sums/factors computed fp32, and the tile
downcast once on store), halving the bytes moved by this bandwidth-bound
kernel. bf16 tiles want block_m a multiple of 16 (see ops.sublane_for);
``ops.pick_block_m`` budgets VMEM with the two itemsizes separately.

Cost source: this kernel *loads* its tile — the initial coupling must
exist in HBM. For implicit geometries (point clouds), the solve's colsum
and first-iteration passes have tile-COMPUTE twins in ``uot_geometry``
that evaluate the Gibbs tile in VMEM from coordinates, after which the
coupling is ordinary solver state and these kernels take over (the
``geometry=`` path of ``ops.solve_fused``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.vmem import COMPILER_PARAMS


def _safe_pow(target, sums, fi: float):
    """(target / sums) ** fi with 0-sum guard (matches core.rescale_factors)."""
    safe = jnp.where(sums > 0, sums, 1.0)
    ratio = jnp.where(sums > 0, target / safe, 1.0)
    if fi == 1.0:
        return ratio
    return jnp.power(ratio, fi)


def _fused_iter_kernel(fcol_ref, a_ref, A_ref, out_ref, colsum_ref, *,
                       fi: float, acc_dtype):
    i = pl.program_id(0)

    blk = A_ref[...].astype(acc_dtype)          # (bm, N)
    fcol = fcol_ref[...].astype(acc_dtype)      # (1, N)

    blk = blk * fcol                             # I: column rescale
    rowsum = jnp.sum(blk, axis=1, keepdims=True)  # II: row sums (bm, 1)
    frow = _safe_pow(a_ref[...].astype(acc_dtype), rowsum, fi)
    blk = blk * frow                             # III: row rescale

    out_ref[...] = blk.astype(out_ref.dtype)

    # IV: accumulate next iteration's column sums. Grid steps run
    # sequentially on TPU, so the revisited (1, N) accumulator block needs
    # no synchronization (the pthread-join / atomicAdd of the paper).
    @pl.when(i == 0)
    def _init():
        colsum_ref[...] = jnp.zeros_like(colsum_ref)

    colsum_ref[...] += jnp.sum(blk, axis=0, keepdims=True).astype(colsum_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("fi", "block_m", "interpret", "acc_dtype"))
def fused_iteration(A: jax.Array, factor_col: jax.Array, a: jax.Array, *,
                    fi: float, block_m: int = 256, interpret: bool = False,
                    acc_dtype=jnp.float32):
    """One MAP-UOT iteration. A: (M, N); factor_col: (N,); a: (M,).

    Shapes must be pre-padded: M % block_m == 0 and N % 128 == 0 (the ops.py
    wrapper pads with zeros, which the rescaling math is invariant to).

    Returns (A_next, next_colsum) with next_colsum fp32 of shape (N,).

    A_next is written over A's buffer (``input_output_aliases``): every
    grid step reads tile i and writes the same tile, and the prefetch of
    tile i+1 never touches tile i's write-back. So a loop that carries A
    moves the coupling once each way per iteration and copies nothing. A
    caller that keeps its A sees it unchanged: XLA copies an argument
    that is still live (or not donated) before the call.
    """
    M, N = A.shape
    assert M % block_m == 0, (M, block_m)
    grid = (M // block_m,)

    kernel = functools.partial(_fused_iter_kernel, fi=fi, acc_dtype=acc_dtype)
    out, colsum = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, N), lambda i: (0, 0)),        # factor_col
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),  # a (RPD)
            pl.BlockSpec((block_m, N), lambda i: (i, 0)),  # A tile
        ],
        out_specs=[
            pl.BlockSpec((block_m, N), lambda i: (i, 0)),  # A' tile
            pl.BlockSpec((1, N), lambda i: (0, 0)),        # colsum acc
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), A.dtype),
            jax.ShapeDtypeStruct((1, N), acc_dtype),
        ],
        input_output_aliases={2: 0},
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(factor_col.reshape(1, N), a.reshape(M, 1), A)
    return out, colsum.reshape(N)


def _colsum_only_kernel(A_ref, colsum_ref, *, acc_dtype):
    """Initial column sums (the Algorithm 1 'preprocessing' pass)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        colsum_ref[...] = jnp.zeros_like(colsum_ref)

    colsum_ref[...] += jnp.sum(
        A_ref[...].astype(acc_dtype), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret", "acc_dtype"))
def colsum(A: jax.Array, *, block_m: int = 256, interpret: bool = False,
           acc_dtype=jnp.float32):
    M, N = A.shape
    assert M % block_m == 0
    out = pl.pallas_call(
        functools.partial(_colsum_only_kernel, acc_dtype=acc_dtype),
        grid=(M // block_m,),
        in_specs=[pl.BlockSpec((block_m, N), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, N), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, N), acc_dtype),
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(A)
    return out.reshape(N)
