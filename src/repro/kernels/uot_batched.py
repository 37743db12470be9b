"""Batched MAP-UOT fused Pallas kernels: a stack of problems in one launch.

Serving solves *many* small/medium UOT problems per step, not one large one.
A Python loop of single-problem kernels pays B dispatches (and B paddings);
a naive ``vmap`` of the jnp solver loses the explicit single-pass schedule.
These kernels instead run Algorithm 1 over a 2-D grid ``(batch, row_blocks)``
with the row dimension innermost, so each problem keeps the HBM-minimal
read+write-once schedule and its own ``(1, N)`` column-sum accumulator block
(revisited across consecutive grid steps — the TPU revisit rule — exactly as
in the single-problem kernel; the batch dimension just concatenates those
per-problem sequential sweeps).

Mixed precision: ``A`` may be stored bf16 while every reduction/factor stays
fp32 (``acc_dtype``). On a bandwidth-bound kernel this halves bytes moved:
per problem per iteration the traffic is ``M*N*(itemsize_in + itemsize_out)``
bytes + O(M + N), i.e. 2 MB/iter for a 512x512 fp32 problem and 1 MB bf16.

Shapes are pre-padded by ``ops.solve_fused_batched`` (zero rows/cols are
no-ops for the rescaling math, proven for the single-problem path and
re-asserted for this one in tests/test_batched.py).

Cost source: these kernels *load* their tiles. The implicit-geometry
solve (``ops.solve_fused_batched(geometry=...)``) replaces the initial
colsum and iteration-1 launches with the tile-compute twins in
``uot_geometry`` (Gibbs tiles evaluated in VMEM from coordinates, masked
per-problem valid counts standing in for zero padding), then continues
with these kernels from iteration 2 — bit-identical iterates either way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.uot_fused import _safe_pow
from repro.kernels.vmem import COMPILER_PARAMS


def _batched_fused_iter_kernel(fcol_ref, a_ref, A_ref, out_ref, colsum_ref, *,
                               fi: float, acc_dtype):
    i = pl.program_id(1)  # row block within the current problem (innermost)

    blk = A_ref[...].astype(acc_dtype)           # (1, bm, N)
    fcol = fcol_ref[...].astype(acc_dtype)       # (1, 1, N)

    blk = blk * fcol                             # I: column rescale
    rowsum = jnp.sum(blk, axis=2, keepdims=True)  # II: (1, bm, 1)
    frow = _safe_pow(a_ref[...].astype(acc_dtype), rowsum, fi)
    blk = blk * frow                             # III: row rescale

    out_ref[...] = blk.astype(out_ref.dtype)

    # IV: per-problem column-sum accumulator. With the row dimension
    # innermost, problem b's (1, 1, N) accumulator block sees its
    # row-block steps consecutively, so no cross-problem interleaving.
    @pl.when(i == 0)
    def _init():
        colsum_ref[...] = jnp.zeros_like(colsum_ref)

    colsum_ref[...] += jnp.sum(blk, axis=1, keepdims=True).astype(colsum_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("fi", "block_m", "interpret", "acc_dtype"))
def batched_fused_iteration(A: jax.Array, factor_col: jax.Array,
                            a: jax.Array, *, fi: float, block_m: int = 256,
                            interpret: bool = False, acc_dtype=jnp.float32):
    """One MAP-UOT iteration for a stack of problems.

    A: (B, M, N); factor_col: (B, N); a: (B, M). M % block_m == 0 and
    N % 128 == 0 (pre-padded by the ops wrapper). Returns
    (A_next, next_colsum) with next_colsum of shape (B, N) in acc_dtype.

    A_next is written over A's buffer (``input_output_aliases``): every
    grid step reads tile (b, i) and writes the same tile, and the prefetch
    of the next tile never touches the one being written back. So a loop
    that carries A moves the coupling once each way per iteration and
    copies nothing. A caller that keeps its A sees it unchanged: XLA
    copies an argument that is still live (or not donated) before the
    call.
    """
    B, M, N = A.shape
    assert M % block_m == 0, (M, block_m)
    grid = (B, M // block_m)

    kernel = functools.partial(_batched_fused_iter_kernel, fi=fi,
                               acc_dtype=acc_dtype)
    out, colsum = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, N), lambda b, i: (b, 0, 0)),        # fcol
            pl.BlockSpec((1, block_m, 1), lambda b, i: (b, i, 0)),  # a (RPD)
            pl.BlockSpec((1, block_m, N), lambda b, i: (b, i, 0)),  # A tile
        ],
        out_specs=[
            pl.BlockSpec((1, block_m, N), lambda b, i: (b, i, 0)),  # A' tile
            pl.BlockSpec((1, 1, N), lambda b, i: (b, 0, 0)),        # colsum
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, M, N), A.dtype),
            jax.ShapeDtypeStruct((B, 1, N), acc_dtype),
        ],
        input_output_aliases={2: 0},
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(factor_col.reshape(B, 1, N), a.reshape(B, M, 1), A)
    return out, colsum.reshape(B, N)


def _batched_fused_iter_frow_kernel(mask_ref, fcol_ref, a_ref, A_ref,
                                    out_ref, colsum_ref, frow_ref, *,
                                    fi: float, acc_dtype):
    i = pl.program_id(1)

    blk_in = A_ref[...].astype(acc_dtype)        # (1, bm, N)
    fcol = fcol_ref[...].astype(acc_dtype)       # (1, 1, N)

    blk = blk_in * fcol                          # I: column rescale
    rowsum = jnp.sum(blk, axis=2, keepdims=True)  # II: (1, bm, 1)
    frow = _safe_pow(a_ref[...].astype(acc_dtype), rowsum, fi)
    blk = blk * frow                             # III: row rescale

    # Lane freeze happens HERE, inside the single pass: a masked-out lane
    # writes back its input tile unchanged (bit-exact), so freezing costs
    # no extra memory pass — the tile was already in VMEM.
    blk = jnp.where(mask_ref[...] > 0, blk, blk_in)

    out_ref[...] = blk.astype(out_ref.dtype)
    frow_ref[...] = frow.astype(frow_ref.dtype)

    @pl.when(i == 0)
    def _init():
        colsum_ref[...] = jnp.zeros_like(colsum_ref)

    colsum_ref[...] += jnp.sum(blk, axis=1, keepdims=True).astype(colsum_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("fi", "block_m", "interpret", "acc_dtype",
                              "in_place"))
def batched_fused_iteration_frow(A: jax.Array, factor_col: jax.Array,
                                 a: jax.Array, mask: jax.Array, *, fi: float,
                                 block_m: int = 256, interpret: bool = False,
                                 acc_dtype=jnp.float32, in_place: bool = True):
    """One masked batched MAP-UOT iteration that also emits the row factors.

    The steppable-solver form of ``batched_fused_iteration``: ``mask``
    (B,) float (1.0 = update, 0.0 = frozen) selects per lane between the
    rescaled tile and the unchanged input *inside* the kernel — same
    read+write-once traffic as the unmasked kernel, no second pass — and a
    third output returns the per-row rescale factors ``frow`` (B, M) (an
    O(M)-per-problem write, negligible against the M*N tile traffic) so
    the caller can observe the per-lane stationarity drift. A frozen
    lane's colsum output is the recomputation from its unchanged tile;
    ``ops._stepped_iter`` re-selects the carried value so bf16 storage
    keeps carried-colsum semantics. Returns (A_next, next_colsum, frow);
    frow is the *computed* factor even for frozen lanes (callers mask it).

    A_next is written over A's buffer (``input_output_aliases``), as in
    ``batched_fused_iteration``; a frozen lane writes its input tile back
    unchanged. A caller that keeps its A sees it unchanged: XLA copies an
    argument that is still live (or not donated) before the call.
    ``in_place=False`` writes a new buffer instead, for the first
    iteration of a solve whose A is the caller's, which the solve's loop
    then owns: the caller's A is read once and never copied.
    """
    B, M, N = A.shape
    assert M % block_m == 0, (M, block_m)
    grid = (B, M // block_m)

    kernel = functools.partial(_batched_fused_iter_frow_kernel, fi=fi,
                               acc_dtype=acc_dtype)
    out, colsum, frow = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1), lambda b, i: (b, 0, 0)),        # mask
            pl.BlockSpec((1, 1, N), lambda b, i: (b, 0, 0)),        # fcol
            pl.BlockSpec((1, block_m, 1), lambda b, i: (b, i, 0)),  # a (RPD)
            pl.BlockSpec((1, block_m, N), lambda b, i: (b, i, 0)),  # A tile
        ],
        out_specs=[
            pl.BlockSpec((1, block_m, N), lambda b, i: (b, i, 0)),  # A' tile
            pl.BlockSpec((1, 1, N), lambda b, i: (b, 0, 0)),        # colsum
            pl.BlockSpec((1, block_m, 1), lambda b, i: (b, i, 0)),  # frow
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, M, N), A.dtype),
            jax.ShapeDtypeStruct((B, 1, N), acc_dtype),
            jax.ShapeDtypeStruct((B, M, 1), acc_dtype),
        ],
        input_output_aliases={3: 0} if in_place else {},
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(mask.reshape(B, 1, 1).astype(jnp.float32),
      factor_col.reshape(B, 1, N), a.reshape(B, M, 1), A)
    return out, colsum.reshape(B, N), frow.reshape(B, M)


def _batched_colsum_kernel(A_ref, colsum_ref, *, acc_dtype):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        colsum_ref[...] = jnp.zeros_like(colsum_ref)

    colsum_ref[...] += jnp.sum(
        A_ref[...].astype(acc_dtype), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret",
                                             "acc_dtype"))
def batched_colsum(A: jax.Array, *, block_m: int = 256,
                   interpret: bool = False, acc_dtype=jnp.float32):
    """Per-problem initial column sums: (B, M, N) -> (B, N)."""
    B, M, N = A.shape
    assert M % block_m == 0
    out = pl.pallas_call(
        functools.partial(_batched_colsum_kernel, acc_dtype=acc_dtype),
        grid=(B, M // block_m),
        in_specs=[pl.BlockSpec((1, block_m, N), lambda b, i: (b, i, 0))],
        out_specs=pl.BlockSpec((1, 1, N), lambda b, i: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, N), acc_dtype),
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(A)
    return out.reshape(B, N)


def _batched_uv_iter_kernel(v_ref, a_ref, K_ref, u_ref, ktu_ref, *,
                            fi: float, acc_dtype):
    i = pl.program_id(1)

    blk = K_ref[...].astype(acc_dtype)            # (1, bm, N) read-only
    v = v_ref[...].astype(acc_dtype)              # (1, 1, N)

    Kv = jnp.sum(blk * v, axis=2, keepdims=True)  # (1, bm, 1)
    u = _safe_pow(a_ref[...].astype(acc_dtype), Kv, fi)
    u_ref[...] = u.astype(u_ref.dtype)

    @pl.when(i == 0)
    def _init():
        ktu_ref[...] = jnp.zeros_like(ktu_ref)

    ktu_ref[...] += jnp.sum(blk * u, axis=1, keepdims=True).astype(ktu_ref.dtype)


@functools.partial(jax.jit, static_argnames=("fi", "block_m", "interpret",
                                             "acc_dtype"))
def batched_uv_iteration(K: jax.Array, v: jax.Array, a: jax.Array, *,
                         fi: float, block_m: int = 256,
                         interpret: bool = False, acc_dtype=jnp.float32):
    """Batched read-only u/v pass: K (B, M, N), v (B, N), a (B, M).

    Returns (u, KTu) of shapes (B, M) and (B, N) in acc_dtype.
    """
    B, M, N = K.shape
    assert M % block_m == 0
    u, ktu = pl.pallas_call(
        functools.partial(_batched_uv_iter_kernel, fi=fi, acc_dtype=acc_dtype),
        grid=(B, M // block_m),
        in_specs=[
            pl.BlockSpec((1, 1, N), lambda b, i: (b, 0, 0)),        # v
            pl.BlockSpec((1, block_m, 1), lambda b, i: (b, i, 0)),  # a
            pl.BlockSpec((1, block_m, N), lambda b, i: (b, i, 0)),  # K tile
        ],
        out_specs=[
            pl.BlockSpec((1, block_m, 1), lambda b, i: (b, i, 0)),  # u
            pl.BlockSpec((1, 1, N), lambda b, i: (b, 0, 0)),        # K^T u
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, M, 1), acc_dtype),
            jax.ShapeDtypeStruct((B, 1, N), acc_dtype),
        ],
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(v.reshape(B, 1, N), a.reshape(B, M, 1), K)
    return u.reshape(B, M), ktu.reshape(B, N)


def _batched_materialize_kernel(u_ref, v_ref, K_ref, P_ref, *, acc_dtype):
    blk = K_ref[...].astype(acc_dtype)
    P_ref[...] = (blk * u_ref[...].astype(acc_dtype)
                  * v_ref[...].astype(acc_dtype)).astype(P_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret",
                                             "acc_dtype", "out_dtype"))
def batched_materialize_coupling(K: jax.Array, u: jax.Array, v: jax.Array, *,
                                 block_m: int = 256, interpret: bool = False,
                                 acc_dtype=jnp.float32, out_dtype=jnp.float32):
    """P_b = diag(u_b) K_b diag(v_b) for every problem in the stack."""
    B, M, N = K.shape
    assert M % block_m == 0
    P = pl.pallas_call(
        functools.partial(_batched_materialize_kernel, acc_dtype=acc_dtype),
        grid=(B, M // block_m),
        in_specs=[
            pl.BlockSpec((1, block_m, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, N), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_m, N), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_m, N), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, M, N), out_dtype),
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(u.reshape(B, M, 1), v.reshape(B, 1, N), K)
    return P
