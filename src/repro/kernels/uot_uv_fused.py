"""Beyond-paper kernel: one READ-ONLY pass per u/v Sinkhorn iteration.

In the POT u/v-potential form the Gibbs kernel K never changes; an iteration
needs (K v) and (K^T u_new). The same interweaving insight that MAP-UOT
applies to the matrix-scaling form applies here with an even better traffic
bound: while streaming row block i to compute (K v)_i, the fresh
u_i = (a_i / (K v)_i)^fi is immediately available, so u_i * K[i, :] can be
accumulated into the K^T u partials during the SAME pass.

HBM traffic per iteration: M*N element READS, ZERO matrix writes
(vs MAP-UOT's MN read + MN write). K can additionally be stored bf16
(accumulators fp32), halving bytes again: total up to 12x less traffic than
the fp32 POT baseline.

    grid step i:
        blk = K[i*bm:(i+1)*bm, :]                 # read-only tile
        Kv_i = (blk * v[None, :]).sum(1)          # matvec piece
        u_i = (a_i / Kv_i) ** fi
        ktu_acc += (blk * u_i[:, None]).sum(0)    # transposed matvec piece
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.uot_fused import _safe_pow
from repro.kernels.vmem import COMPILER_PARAMS


def _uv_iter_kernel(v_ref, a_ref, K_ref, u_ref, ktu_ref, *, fi: float,
                    acc_dtype):
    i = pl.program_id(0)

    blk = K_ref[...].astype(acc_dtype)           # (bm, N) read-only
    v = v_ref[...].astype(acc_dtype)             # (1, N)

    Kv = jnp.sum(blk * v, axis=1, keepdims=True)  # (bm, 1)
    u = _safe_pow(a_ref[...].astype(acc_dtype), Kv, fi)
    u_ref[...] = u.astype(u_ref.dtype)

    @pl.when(i == 0)
    def _init():
        ktu_ref[...] = jnp.zeros_like(ktu_ref)

    ktu_ref[...] += jnp.sum(blk * u, axis=0, keepdims=True).astype(ktu_ref.dtype)


@functools.partial(jax.jit, static_argnames=("fi", "block_m", "interpret",
                                             "acc_dtype"))
def uv_iteration(K: jax.Array, v: jax.Array, a: jax.Array, *, fi: float,
                 block_m: int = 256, interpret: bool = False,
                 acc_dtype=jnp.float32):
    """One u/v iteration's matrix work in a single read pass.

    Returns (u, KTu) — the caller finishes with v' = (b / KTu) ** fi (O(N)).
    """
    M, N = K.shape
    assert M % block_m == 0
    u, ktu = pl.pallas_call(
        functools.partial(_uv_iter_kernel, fi=fi, acc_dtype=acc_dtype),
        grid=(M // block_m,),
        in_specs=[
            pl.BlockSpec((1, N), lambda i: (0, 0)),        # v
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),  # a
            pl.BlockSpec((block_m, N), lambda i: (i, 0)),  # K tile
        ],
        out_specs=[
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),  # u
            pl.BlockSpec((1, N), lambda i: (0, 0)),        # K^T u acc
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, 1), acc_dtype),
            jax.ShapeDtypeStruct((1, N), acc_dtype),
        ],
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(v.reshape(1, N), a.reshape(M, 1), K)
    return u.reshape(M), ktu.reshape(N)


def _materialize_kernel(u_ref, v_ref, K_ref, P_ref, *, acc_dtype):
    blk = K_ref[...].astype(acc_dtype)
    P_ref[...] = (blk * u_ref[...].astype(acc_dtype)
                  * v_ref[...].astype(acc_dtype)).astype(P_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret",
                                             "acc_dtype", "out_dtype"))
def materialize_coupling(K: jax.Array, u: jax.Array, v: jax.Array, *,
                         block_m: int = 256, interpret: bool = False,
                         acc_dtype=jnp.float32, out_dtype=jnp.float32):
    """P = diag(u) K diag(v) — one final pass after the solve."""
    M, N = K.shape
    assert M % block_m == 0
    P = pl.pallas_call(
        functools.partial(_materialize_kernel, acc_dtype=acc_dtype),
        grid=(M // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
            pl.BlockSpec((block_m, N), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(u.reshape(M, 1), v.reshape(1, N), K)
    return P
