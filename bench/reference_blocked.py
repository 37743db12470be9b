"""Plain fp32 ``jax.numpy`` reference of the matrix-scaling UOT solve,
for problems whose Gibbs kernel is too large to keep beside the program's
output.

The cell's yardstick where ``bench/reference.py`` cannot fit: it runs the
same iteration and stopping rule, but never holds K or the coupling.
Written with running row and column factors ``u`` and ``v``,
``A_t = diag(u_t) K diag(v_t)``, it recomputes K's row blocks from the
points in every pass. It imports nothing of the program under test and
takes nothing it made: the cell gives it the same seeded points.

One iteration, as ``reference.iterate`` on ``A_t`` (``fi = reg_m / (reg_m
+ reg)``; a sum of 0 gives the factor 1)::

    fcol = (b / colsum(A)) ** fi,  colsum(A) = v * (K^T u)
    v   <- v * fcol
    frow = (a / rowsum(A)) ** fi,  rowsum(A) = u * (K v)
    u   <- u * frow

With ``tol`` set the solve stops after the first iteration whose row
factors moved by at most ``tol`` from the previous iteration's (the first
compares with all ones), or after ``num_iters`` iterations. One pass over
K's row blocks per iteration gives ``K v`` for the block's rows, then
their new ``u``, then their share of ``K^T u`` for the next iteration.

Every reduction is an fp32 elementwise sum: there is no matrix product, so
no matmul precision setting applies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import factors


def gibbs(x, y, reg: float):
    """``exp(-C / reg)``, ``C_ij = |x_i - y_j|^2 / 2`` for points of the
    plane: the Gibbs kernel of the solve cells' dense problem."""
    C = ((x[:, None, 0] - y[None, :, 0]) ** 2
         + (x[:, None, 1] - y[None, :, 1]) ** 2) / 2.0
    return jnp.exp(-C / reg)


@functools.partial(jax.jit, static_argnames=(
    "reg", "exponent", "tol", "num_iters", "block", "k_dtype"))
def solve(x, y, a, b, *, reg: float, exponent: float, tol: float | None,
          num_iters: int, block: int = 1024, k_dtype=jnp.float32):
    """Solve the problem of points ``x`` (M, 2), ``y`` (N, 2) and
    marginals ``a``, ``b``, in row blocks of ``block`` (M a multiple).

    Returns ``(u, v, colsum, iters, drift)``: the factors of the coupling
    ``diag(u) K diag(v)``, its column sums, the number of iterations run
    and the last iteration's row-factor drift. ``k_dtype`` is the type K
    is rounded to once, as it is computed (each iteration computes in
    fp32): fp32 is the reference; bfloat16 makes the control, the
    reference in the nearest precision below the configuration's.
    """
    M, N = x.shape[0], y.shape[0]
    nb = M // block
    xb = x.reshape(nb, block, 2)
    ab = a.reshape(nb, block)

    def kblock(xi):
        return rounded(gibbs(xi, y, reg), k_dtype)

    def ktu_pass(u):
        def step(acc, xs):
            xi, ui = xs
            return acc + (kblock(xi) * ui[:, None]).sum(axis=0), None
        return jax.lax.scan(step, jnp.zeros((N,), jnp.float32),
                            (xb, u.reshape(nb, block)))[0]

    def body(carry):
        u, v, ktu, prev, it, _ = carry
        v = v * factors(b, v * ktu, exponent)

        def step(acc, xs):
            xi, ui, ai = xs
            Kb = kblock(xi)
            frow = factors(ai, ui * (Kb * v[None, :]).sum(axis=1), exponent)
            ui = ui * frow
            return acc + (Kb * ui[:, None]).sum(axis=0), (ui, frow)

        ktu, (u, frow) = jax.lax.scan(
            step, jnp.zeros((N,), jnp.float32),
            (xb, u.reshape(nb, block), ab))
        u, frow = u.reshape(M), frow.reshape(M)
        return u, v, ktu, frow, it + 1, jnp.max(jnp.abs(frow - prev))

    def cond(carry):
        it, drift = carry[4], carry[5]
        keep = it < num_iters
        return keep if tol is None else keep & (drift > tol)

    ones_m = jnp.ones((M,), jnp.float32)
    u, v, ktu, _, iters, drift = jax.lax.while_loop(
        cond, body, (ones_m, jnp.ones((N,), jnp.float32), ktu_pass(ones_m),
                     ones_m, jnp.int32(0), jnp.float32(jnp.inf)))
    return u, v, v * ktu, iters, drift


def rounded(K, k_dtype):
    """K rounded to ``k_dtype``'s precision, kept in fp32. The rounding is
    ``lax.reduce_precision``: XLA may drop a cast to a narrower type and
    back inside a fusion (it does on a v5e), never this."""
    if jnp.dtype(k_dtype) == jnp.float32:
        return K
    f = jnp.finfo(k_dtype)
    return jax.lax.reduce_precision(K, exponent_bits=f.nexp,
                                    mantissa_bits=f.nmant)


def coupling(x, y, u, v, *, reg: float, k_dtype=jnp.float32):
    """Rows ``diag(u) K diag(v)`` of the points ``x`` (with their ``u``)."""
    return u[:, None] * rounded(gibbs(x, y, reg), k_dtype) * v[None, :]
