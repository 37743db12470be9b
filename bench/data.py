"""Seeded inputs of the cells: the solve cells' Gibbs kernels, made on the
device in one jitted call, and the service requests' arrays, made on the
host because the scheduler's ``submit`` takes host arrays."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int):
    """A PRNG key that keeps all bits of a seed wider than 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def rng_from_seed(seed: int, stream: int = 0) -> np.random.Generator:
    """A NumPy generator for one stream of a seed (any non-negative int)."""
    return np.random.default_rng([stream, seed])


# The one dense problem of the solve cells; a seed orders its rows and
# columns, so that every seed asks for the same iterations.
BASE_SEED = 0


@functools.partial(jax.jit, static_argnames=("M", "N", "reg", "mass_b"))
def _gibbs_2d(key, *, M: int, N: int, reg: float, mass_b: float):
    kx, ky, ka, kb = jax.random.split(jax.random.key(BASE_SEED), 4)
    kr, kc = jax.random.split(key)
    rows = jax.random.permutation(kr, M)
    cols = jax.random.permutation(kc, N)
    x = jax.random.uniform(kx, (M, 2))[rows]
    y = jax.random.uniform(ky, (N, 2))[cols]
    a = jax.random.uniform(ka, (M,), minval=0.5, maxval=1.5)[rows]
    b = jax.random.uniform(kb, (N,), minval=0.5, maxval=1.5)[cols]
    C = ((x[:, None, 0] - y[None, :, 0]) ** 2
         + (x[:, None, 1] - y[None, :, 1]) ** 2) / 2.0
    return jnp.exp(-C / reg), a / a.sum(), b / b.sum() * mass_b


def gibbs_2d(seed: int, data: dict, reg: float):
    """``(K, a, b)`` of the solve cells' dense problem: the Gibbs kernel of
    half the squared distance between uniform points of the unit square
    (cost in [0, 1]), row marginal of mass 1, column marginal of mass
    ``mass_b``. The points and marginals are drawn once, from
    ``BASE_SEED``; ``seed`` permutes the rows and the columns. A problem
    drawn anew for each seed stops after 10 to 12 iterations at 20480²,
    which would make the seed change the work.
    """
    return _gibbs_2d(key_from_seed(seed), M=data["M"], N=data["N"],
                     reg=reg, mass_b=data["mass_b"])


def marginals(rng: np.random.Generator, M: int, N: int, mass_b: float):
    a = rng.uniform(0.5, 1.5, M).astype(np.float32)
    b = rng.uniform(0.5, 1.5, N).astype(np.float32)
    return a / a.sum(), b / b.sum() * np.float32(mass_b)


# The stream that draws a service request's problem from its ``base``
# number alone, the same for every seed.
BASE_STREAM = 4


def request_arrays(rng: np.random.Generator, spec, data: dict, reg: float):
    """Host arrays of one service request.

    The problem is drawn from the request's ``base`` number alone; ``rng``
    (the run's seed) permutes its rows and columns. So every seed serves
    the same problems, in another order, and asks for the same
    iterations. Dense: ``K = exp(-C / reg)`` with ``C`` uniform on
    [0, 1]. Points: coordinates uniform on the unit cube of ``spec.d``
    dimensions, cost ``|x - y|^2 / d``.
    """
    base = np.random.default_rng([BASE_STREAM, spec.base])
    rows, cols = rng.permutation(spec.M), rng.permutation(spec.N)
    a, b = marginals(base, spec.M, spec.N, data["mass_b"])
    a, b = a[rows], b[cols]
    if spec.kind == "dense":
        C = base.random((spec.M, spec.N), dtype=np.float32)[rows][:, cols]
        return {"K": np.exp(-C / np.float32(reg)), "a": a, "b": b}
    x = base.random((spec.M, spec.d), dtype=np.float32)[rows]
    y = base.random((spec.N, spec.d), dtype=np.float32)[cols]
    return {"x": x, "y": y, "a": a, "b": b}
