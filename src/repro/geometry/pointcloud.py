"""Point-cloud squared-Euclidean geometry: cost tiles from coordinates.

For point-cloud workloads the cost ``C_ij = ||x_i - y_j||^2`` is a function
of ``O((M + N) * d)`` coordinate data, so a dense ``C`` in HBM is pure
wasted bandwidth (Lakshmanan & Pichler, arXiv:2306.13618, make the same
observation for fast UOT kernel evaluation). This module holds both

- the ``PointCloudGeometry`` pytree (coordinates + squared norms + an
  optional per-problem valid-count mask for zero-padded batches), and
- the **shared tile arithmetic** (``pairwise_dot`` / ``cost_tile`` /
  ``gibbs_tile``) that every consumer — the materializing jnp mirrors
  here, the streamed Pallas kernels in ``kernels.uot_geometry``, and the
  resident kernel in ``kernels.uot_resident`` — evaluates.

Bitwise-reproducibility rules (tests/test_geometry.py asserts the result):

1. **Squared norms are precomputed once**, at geometry construction, by a
   standalone jitted helper, and carried as concrete arrays. Recomputing
   ``sum_k x_k^2`` inside each consumer would put the same ``mul+add``
   chain into different XLA fusion contexts, where FMA contraction fires
   differently and the low bits diverge.
2. **The pairwise dot is an unrolled elementwise sum over d** (d is small:
   2-8 for the targeted workloads), not a gemm. A gemm's accumulation
   order depends on how the backend tiles it, so a full-matrix matmul and
   a row-block tile matmul round differently; an unrolled elementwise
   chain is blocking-invariant.
3. ``reg`` and ``scale`` enter as **static Python floats** baked into the
   jaxpr, so the division lowers identically everywhere.

Under those rules the materialized mirror ``kernel(reg)`` and the on-chip
tile evaluation produce bit-identical fp32 values, which is what lets the
ops dispatcher route between the dense-load and tile-compute paths without
changing couplings.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.scipy.special import logsumexp

from repro.geometry.base import Geometry


def sq_norms(p: jax.Array) -> jax.Array:
    """``||p_k||^2`` over the last axis, unrolled: (..., K, d) -> (..., K)."""
    n = p[..., 0] * p[..., 0]
    for k in range(1, p.shape[-1]):
        n = n + p[..., k] * p[..., k]
    return n


_sq_norms_jit = jax.jit(sq_norms)


def pairwise_dot(x: jax.Array, y: jax.Array) -> jax.Array:
    """``x @ y^T`` over the last axis as an unrolled elementwise sum.

    x: (..., m, d); y: (..., n, d) -> (..., m, n). Rule 2 above: the
    unrolled chain rounds identically whether evaluated on the full
    matrix or on a row-block tile, which a gemm does not guarantee.
    """
    d = x.shape[-1]
    out = x[..., :, 0:1] * y[..., :, 0][..., None, :]
    for k in range(1, d):
        out = out + x[..., :, k:k + 1] * y[..., :, k][..., None, :]
    return out


def cost_tile(x, xn, y, yn, *, scale: float = 1.0) -> jax.Array:
    """``(||x_i - y_j||^2) / scale`` for a coordinate tile.

    x: (..., m, d); xn: (..., m, 1); y: (..., n, d); yn: (..., 1, n).
    The norms are taken as inputs (rule 1), the dot is unrolled (rule 2),
    ``scale`` is a static float (rule 3).
    """
    sq = xn + yn - 2.0 * pairwise_dot(x, y)
    if scale != 1.0:
        sq = sq / scale
    return sq


def gibbs_tile(x, xn, y, yn, *, reg: float, scale: float = 1.0,
               barrier: bool = True) -> jax.Array:
    """``exp(-cost_tile / reg)`` — the Gibbs-kernel tile, computed with the
    exact arithmetic of the two-step dense path (materialize ``C``, then
    exponentiate).

    The ``optimization_barrier`` between the two steps is load-bearing for
    bitwise parity (rule 4, as it were): without it XLA *rematerializes*
    the cost chain inside the exp fusion, where FMA contraction can round
    an ulp differently than the standalone cost computation — so
    ``exp(-stored_C / reg)`` and the fused evaluation would disagree in
    the low bit. The barrier pins the exp's input to exactly the value
    the dense path stores. (Rounding, not performance: the barrier cuts
    one fusion edge on an elementwise chain.)

    Compiled Pallas kernels pass ``barrier=False``: Mosaic has no lowering
    for the barrier and no XLA fusion for it to cut, so on the chip the
    tile matches the XLA mirror to a tolerance, not bit for bit.
    """
    sq = cost_tile(x, xn, y, yn, scale=scale)
    if barrier:
        sq = jax.lax.optimization_barrier(sq)
    return jnp.exp(-sq / reg)


def valid_mask(m: int, n: int, m_valid, n_valid) -> jax.Array:
    """(..., m, n) bool mask of in-bounds entries for zero-padded problems.

    ``m_valid`` / ``n_valid`` are int scalars or (...,) arrays (one count
    per batched problem). Entries at or beyond the valid counts must be
    *exactly zero* in any materialized kernel/coupling — that is what
    makes zero-padding a no-op for the rescaling math, same as padding a
    dense matrix with zero rows/cols.
    """
    rows = jnp.arange(m)
    cols = jnp.arange(n)
    mv = jnp.asarray(m_valid)[..., None, None]
    nv = jnp.asarray(n_valid)[..., None, None]
    return (rows[:, None] < mv) & (cols[None, :] < nv)


_MIRROR_LANE = 128  # evaluate mirrors at the kernel path's lane alignment


@functools.partial(jax.jit, static_argnames=("reg", "scale"))
def _kernel_mirror(x, xn, y, yn, *, reg: float, scale: float) -> jax.Array:
    return gibbs_tile(x, xn[..., :, None], y, yn[..., None, :],
                      reg=reg, scale=scale)


@functools.partial(jax.jit, static_argnames=("scale",))
def _cost_mirror(x, xn, y, yn, *, scale: float) -> jax.Array:
    return cost_tile(x, xn[..., :, None], y, yn[..., None, :], scale=scale)


@dataclasses.dataclass(frozen=True)
class PointCloudGeometry(Geometry):
    """Squared-Euclidean geometry of two coordinate clouds.

    Fields (single problem; a leading batch dim on every array field gives
    a batched geometry, as assembled by the serving layer):
      x, y:   (M, d) / (N, d) fp32 coordinates.
      xn, yn: (M,) / (N,) precomputed squared norms (rule 1 — use
              ``from_points`` unless you already hold them).
      m_valid, n_valid: optional per-problem valid counts (int32 scalars /
              (B,) arrays) for zero-padded stacks; rows/cols beyond them
              evaluate to exactly 0 in every kernel tile. A kernel-path
              construct: ``kernel()`` and the Pallas tile kernels honor
              them, while ``cost()`` and the lazy applications refuse
              masked geometries (slice the clouds instead — only the
              Gibbs kernel has a natural masked value).
      scale:  static cost divisor (``C = ||x - y||^2 / scale``), e.g. a
              known cost bound for normalized-cost applications.

    ``is_implicit=True``: the kernel stack computes this geometry's Gibbs
    tiles in VMEM from the coordinates; no ``M*N`` cost array exists in
    HBM on that path, and a serving request ships ``(M + N) * d`` floats
    instead of ``M * N``.
    """

    x: jax.Array
    y: jax.Array
    xn: jax.Array
    yn: jax.Array
    m_valid: jax.Array | None = None
    n_valid: jax.Array | None = None
    scale: float = 1.0

    @classmethod
    def from_points(cls, x, y, *, scale: float = 1.0,
                    m_valid=None, n_valid=None) -> "PointCloudGeometry":
        """Canonical constructor: precomputes the squared norms once.

        Call outside jit so the norms are concrete (rule 1 in the module
        docstring); inside a trace the stability guarantee is down to the
        caller keeping every consumer in the same trace.
        """
        x = jnp.asarray(x, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        if x.shape[-1] != y.shape[-1]:
            raise ValueError(f"coordinate dims differ: {x.shape} vs {y.shape}")
        return cls(x=x, y=y, xn=_sq_norms_jit(x), yn=_sq_norms_jit(y),
                   m_valid=None if m_valid is None else jnp.asarray(
                       m_valid, jnp.int32),
                   n_valid=None if n_valid is None else jnp.asarray(
                       n_valid, jnp.int32),
                   scale=float(scale))

    is_implicit = True

    @property
    def shape(self) -> tuple[int, int]:
        return (self.x.shape[-2], self.y.shape[-2])

    @property
    def batch_shape(self) -> tuple[int, ...]:
        return tuple(self.x.shape[:-2])

    @property
    def dim(self) -> int:
        return self.x.shape[-1]

    def payload_nbytes(self) -> int:
        """Bytes a serving request carrying this geometry ships —
        coordinates + precomputed squared norms, ``(M + N) * (d + 1)``
        fp32 values per problem — vs ``M * N * 4`` for the dense kernel.

        This O(M + N) payload is what makes coordinate requests cheap to
        *route*: the cluster scheduler can place (or re-place) them on any
        device shard for the cost of a vector transfer, and the M*N Gibbs
        kernel only ever materializes on the owning device at admission
        (``repro.cluster``'s routing decision table cites this number).
        """
        M, N = self.shape
        per_problem = 4 * (M + N) * (self.dim + 1)
        batch = 1
        for dim in self.batch_shape:
            batch *= int(dim)
        return batch * per_problem

    def _lane_padded_cols(self):
        """Eagerly zero-pad the column cloud to the 128-lane multiple the
        kernel path computes at; the mirrors evaluate on the padded shape
        and the caller slices the result back.

        Bitwise rule 4: SIMD and scalar-tail codegen round differently
        (libm scalar exp vs vectorized exp; FMA contraction in the vector
        body only), so an unpadded (M, N) evaluation disagrees with the
        kernel path's lane-padded tiles in the last ``N % vector-width``
        columns. The padding must happen *outside* the jitted mirror —
        a pad fused into the evaluation loop changes its codegen again.
        """
        N = self.y.shape[-2]
        pad = (-N) % _MIRROR_LANE
        if not pad:
            return self.y, self.yn, N
        y = jnp.pad(self.y, [(0, 0)] * (self.y.ndim - 2)
                    + [(0, pad), (0, 0)])
        yn = jnp.pad(self.yn, [(0, 0)] * (self.yn.ndim - 1) + [(0, pad)])
        return y, yn, N

    def cost(self) -> jax.Array:
        """Dense ``C = ||x - y||^2 / scale`` (tests / explicit-C parity).

        Undefined for valid-count-masked geometries (a masked kernel
        entry is 0, i.e. cost +inf — not a usable dense C); slice the
        clouds instead.
        """
        self._require_unmasked("cost()")
        y, yn, N = self._lane_padded_cols()
        return _cost_mirror(self.x, self.xn, y, yn,
                            scale=self.scale)[..., :N]

    def kernel(self, reg: float) -> jax.Array:
        """Materialized Gibbs mirror — bit-identical to the on-chip tiles."""
        y, yn, N = self._lane_padded_cols()
        K = _kernel_mirror(self.x, self.xn, y, yn, reg=float(reg),
                           scale=self.scale)[..., :N]
        if self.m_valid is None and self.n_valid is None:
            return K
        M = self.shape[0]
        mv = M if self.m_valid is None else self.m_valid
        nv = N if self.n_valid is None else self.n_valid
        return jnp.where(valid_mask(M, N, mv, nv), K, 0.0)

    # -- lazy applications (u/v and log-domain solvers): row-chunked so the
    # peak live cost tile is (chunk, N), not (M, N) ------------------------

    _CHUNK = 128

    def _require_unmasked(self, what: str):
        # valid-count masks are a *kernel-path* construct (they stand in
        # for the zero rows/cols of a padded dense stack, and only the
        # Gibbs kernel has a natural masked value, 0). Silently ignoring
        # them here would leak the padded coordinates' exp(0)-sized
        # entries into every reduction, so refuse loudly: for the lazy /
        # cost paths, slice the clouds instead of masking them.
        if self.m_valid is not None or self.n_valid is not None:
            raise ValueError(
                f"{what} is not defined for valid-count-masked geometries;"
                f" slice the coordinate clouds (x[:m], y[:n]) instead")

    def _row_chunks(self):
        M, d = self.x.shape[-2], self.x.shape[-1]
        if len(self.batch_shape):
            raise NotImplementedError(
                "lazy applications are per-problem; batched geometries are "
                "consumed by the batched solve entry points")
        self._require_unmasked("a lazy kernel/lse application")
        pad = (-M) % self._CHUNK
        x = jnp.pad(self.x, ((0, pad), (0, 0)))
        xn = jnp.pad(self.xn, (0, pad))
        return (x.reshape(-1, self._CHUNK, d),
                xn.reshape(-1, self._CHUNK), M)

    def apply_kernel(self, v: jax.Array, reg: float) -> jax.Array:
        reg, scale = float(reg), self.scale
        xc, xnc, M = self._row_chunks()

        def body(args):
            xb, xnb = args
            Kb = gibbs_tile(xb, xnb[:, None], self.y, self.yn[None, :],
                            reg=reg, scale=scale)
            return Kb @ v

        return jax.lax.map(body, (xc, xnc)).reshape(-1)[:M]

    def apply_kernel_T(self, u: jax.Array, reg: float) -> jax.Array:
        reg, scale = float(reg), self.scale
        xc, xnc, M = self._row_chunks()
        uc = jnp.pad(u, (0, (-M) % self._CHUNK)).reshape(-1, self._CHUNK)

        def body(args):
            xb, xnb, ub = args
            Kb = gibbs_tile(xb, xnb[:, None], self.y, self.yn[None, :],
                            reg=reg, scale=scale)
            return ub @ Kb

        return jnp.sum(jax.lax.map(body, (xc, xnc, uc)), axis=0)

    def apply_lse(self, z: jax.Array, reg: float) -> jax.Array:
        reg, scale = float(reg), self.scale
        xc, xnc, M = self._row_chunks()

        def body(args):
            xb, xnb = args
            Cb = cost_tile(xb, xnb[:, None], self.y, self.yn[None, :],
                           scale=scale)
            return logsumexp((z[None, :] - Cb) / reg, axis=1)

        return jax.lax.map(body, (xc, xnc)).reshape(-1)[:M]

    def apply_lse_T(self, z: jax.Array, reg: float) -> jax.Array:
        reg, scale = float(reg), self.scale
        xc, xnc, M = self._row_chunks()
        # padded rows must not contribute: push their terms to -inf
        zc = jnp.pad(z, (0, (-M) % self._CHUNK),
                     constant_values=-jnp.inf).reshape(-1, self._CHUNK)

        def body(args):
            xb, xnb, zb = args
            Cb = cost_tile(xb, xnb[:, None], self.y, self.yn[None, :],
                           scale=scale)
            return logsumexp((zb[:, None] - Cb) / reg, axis=0)

        return logsumexp(jax.lax.map(body, (xc, xnc, zc)), axis=0)


jax.tree_util.register_dataclass(
    PointCloudGeometry,
    data_fields=["x", "y", "xn", "yn", "m_valid", "n_valid"],
    meta_fields=["scale"])
