"""Plain fp32 ``jax.numpy`` reference of the matrix-scaling UOT solve.

This is the yardstick every cell's output is compared with. It imports
nothing of the program under test and takes nothing it made: the cells
give it the same seeded data they give the program.

One iteration, as in MAP-UOT's Algorithm 1 (arXiv:2412.11079) and POT's
coupling-form demo, with ``fi = reg_m / (reg_m + reg)``::

    A <- A * ((b / colsum(A)) ** fi)[None, :]     column rescale
    A <- A * ((a / rowsum(A)) ** fi)[:, None]     row rescale

A sum of 0 gives the factor 1 (0/0 is no rescale). With ``tol`` set the
solve stops after the first iteration whose row factors moved by at most
``tol`` from the previous iteration's (the first iteration compares with
all ones), or after ``num_iters`` iterations.

Every reduction is an fp32 elementwise sum: there is no matrix product, so
no matmul precision setting applies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def fi(reg: float, reg_m: float) -> float:
    """The relaxation exponent ``reg_m / (reg_m + reg)``."""
    return reg_m / (reg_m + reg)


def factors(target, sums, exponent: float):
    """``(target / sums) ** exponent``, with 1 where ``sums`` is 0."""
    ratio = jnp.where(sums > 0, target / jnp.where(sums > 0, sums, 1.0), 1.0)
    return ratio if exponent == 1.0 else ratio ** exponent


def iterate(A, a, b, exponent: float):
    """One iteration; returns the new coupling and its row factors."""
    A = A * factors(b, A.sum(axis=0), exponent)[None, :]
    frow = factors(a, A.sum(axis=1), exponent)
    return A * frow[:, None], frow


@functools.partial(jax.jit, static_argnames=("exponent", "tol", "num_iters",
                                             "dtype"))
def solve(K, a, b, *, exponent: float, tol: float | None, num_iters: int,
          dtype=jnp.float32):
    """Solve from the Gibbs kernel ``K``.

    Returns ``(P, colsum, iters, drift)``: the coupling, its column sums,
    the number of iterations run and the last iteration's row-factor
    drift. ``dtype`` is the type the coupling is kept
    in between iterations (each iteration computes in fp32): fp32 is the
    reference; bfloat16 makes the control, the reference in the nearest
    precision below the configuration's.
    """
    K = K.astype(dtype)

    def body(carry):
        A, prev, it, _ = carry
        A, frow = iterate(A.astype(jnp.float32), a, b, exponent)
        return A.astype(dtype), frow, it + 1, jnp.max(jnp.abs(frow - prev))

    def cond(carry):
        _, _, it, drift = carry
        keep = it < num_iters
        return keep if tol is None else keep & (drift > tol)

    A, _, iters, drift = jax.lax.while_loop(
        cond, body, (K, jnp.ones_like(a), jnp.int32(0),
                     jnp.float32(jnp.inf)))
    return A, A.astype(jnp.float32).sum(axis=0), iters, drift


def gibbs_points(x, y, *, scale: float, reg: float):
    """``exp(-C / reg)`` with ``C_ij = sum_k (x_ik - y_jk)^2 / scale``."""
    C = jnp.sum((x[:, None, :] - y[None, :, :]) ** 2, axis=-1) / scale
    return jnp.exp(-C / reg)


@functools.partial(jax.jit, static_argnames=(
    "exponent", "tol", "num_iters", "scale", "reg"))
def solve_points_batch(x, y, a, b, *, scale: float, reg: float,
                       exponent: float, tol: float | None, num_iters: int):
    """``solve`` over a stack of point-cloud problems of one shape."""
    def one(x, y, a, b):
        K = gibbs_points(x, y, scale=scale, reg=reg)
        return solve(K, a, b, exponent=exponent, tol=tol,
                     num_iters=num_iters)
    return jax.vmap(one)(x, y, a, b)


@functools.partial(jax.jit, static_argnames=("exponent", "tol", "num_iters"))
def solve_dense_batch(K, a, b, *, exponent: float, tol: float | None,
                      num_iters: int):
    """``solve`` over a stack of dense problems of one shape."""
    return jax.vmap(lambda K, a, b: solve(
        K, a, b, exponent=exponent, tol=tol, num_iters=num_iters))(K, a, b)
