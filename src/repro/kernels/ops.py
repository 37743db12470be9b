"""jit'd public wrappers over the Pallas UOT kernels.

Handles: zero-padding to hardware-aligned shapes (the rescaling math is
invariant to zero rows/cols), VMEM-aware block-size selection, interpret-mode
fallback on non-TPU backends, and full solver loops assembled from kernels.

Batched & mixed-precision solving
---------------------------------
Serving solves many small/medium problems per step. ``solve_fused_batched``
and ``solve_uv_batched`` run a whole stack of same-shape problems in ONE
kernel launch over a ``(batch, row_blocks)`` grid (see ``uot_batched``),
keeping the per-problem single-pass HBM schedule; ``solve_fused_bucketed``
extends this to ragged problem lists by shape-bucketed zero-padding (pad each
problem to its bucket's (M, N) — zero rows/cols are exact no-ops for the
rescaling math).

All solvers accept a bf16 *storage* mode (``storage_dtype=jnp.bfloat16`` or
``UOTConfig(dtype=jnp.bfloat16)``): the coupling matrix lives in bf16 in
HBM/VMEM while every reduction and rescale factor is computed fp32
(``acc_dtype``). On a bandwidth-bound kernel this halves bytes moved:
fused traffic per problem per iteration is ``M*N*2*itemsize + O(M+N)`` bytes
— 2 MB for 512x512 fp32, 1 MB bf16. ``pick_block_m`` budgets VMEM with the
storage and accumulator itemsizes separately.

Steppable solving (continuous batching)
---------------------------------------
``LaneState`` + ``solve_fused_stepped`` expose the batched solve as
explicit carried state advanced a chunk of iterations per call, with
per-lane ``lane_admit`` / ``lane_evict`` / ``lane_done`` lifecycle — the
substrate for ``repro.serve.scheduler``'s continuous batching, which
admits through ``lane_admit_packed`` (two host buffers, one donated
launch a pool update). With
``cfg.tol`` set, both the stepped and the one-shot batched solves freeze
each lane at the iterate where its row-factor stationarity reaches tol
(identical to the single-problem solvers' early exit, per lane).
``repro.cluster`` stacks per-device ``LaneState`` pools along a mesh axis
and advances every device's pool in one ``shard_map``-ped stepped launch
(the multi-device serving tier); per-lane ``m_valid`` / ``n_valid``
extents let one physical pool host several padded shapes (cross-bucket
lane sharing — see ``lane_admit``).

Resident tier & auto-dispatch
-----------------------------
When a problem's whole padded tile fits the VMEM budget
(``resident_fits``), the streamed per-iteration HBM schedule is beatable:
``uot_resident`` loads each lane's tile on-chip once, iterates to
convergence in a ``lax.while_loop``, and stores once — per-solve instead of
per-iteration traffic. ``impl='auto'`` on the solve entry points routes
between the two tiers by that static budget test (decisions are observable
via ``dispatch_stats``). The budget is only the *fallback*: with a
``dispatch_advisor()`` installed (``repro.obs.measure.MeasuredDispatch``
over a persisted measurement store), a resident-eligible 'auto'
resolution routes by *measured* per-tier cost instead — when both tiers
of the (kernel, shape, dtype, source) cell hold steady-state wall-clock
data, the measured-faster tier wins; cells without data defer to the
static budget. Correctness constraints are never advised away: shapes
over the VMEM budget and sub-fp32 stepped pools stay streamed
regardless of measurements.

Cost geometries
---------------
``geometry=`` on the solve entry points names the cost *source* instead
of a materialized ``A0`` (see ``repro.geometry``). Dense/grid geometries
materialize their Gibbs mirror once and take the historical path. For
implicit geometries (``PointCloudGeometry``) the kernel path computes
Gibbs tiles on-chip from ``O((M + N) * d)`` coordinates — no M*N cost
array ever exists in HBM — and ``resident_fits(implicit=True)`` budgets
only the coupling (no input tile), so shapes the dense tier must stream
run resident under a geometry. Couplings match the dense-load path
bit-for-bit (both dtypes).

Per-solve coupling HBM traffic by (workload x tier x cost source), with
``s`` = storage itemsize, ``T`` = iterations run, ``G`` = the cost-source
read: ``G = M*N*s`` for a dense ``A0`` (materialize/ship + first read)
vs ``G = (M+N)*(d+1)*4`` coordinate bytes for an implicit geometry
(and the solve's write-side first touch of the coupling drops from
"write K then rewrite A1" to "write A1 only"):

====================  ==========================  =========================
workload              resident (fits VMEM;        streamed (over budget)
                      implicit budget is
                      coupling-only)
====================  ==========================  =========================
per-request           ``G + 2*M*N*s`` per solve   ``G + 2*M*N*s * T``
``solve_fused``       (implicit: ``G + M*N*s``    (implicit: the colsum
                      — no tile read, store       pass and iteration 1
                      once)                       read coords, not K)
bucketed batch        ``B*(G + 2*M*N*s)`` per     ``B*(G + 2*M*N*s * T)``
``solve_fused_        chunk solve (one
batched/bucketed``    lane-grid launch, lanes
                      early-exit independently)
scheduler chunk       ``2*L*M*N*s`` per CHUNK     ``2*L*M*N*s *
``solve_fused_        (fp32 pools; bf16 pools     chunk_iters`` per chunk
stepped``             stay streamed to keep       (admission pays ``G``
                      chunk-boundary              once per request either
                      invariance)                 way — coordinates ship
                                                  host->device, K is
                                                  device-materialized)
====================  ==========================  =========================

(+ O(M+N) factor/marginal traffic per launch in every cell. On non-TPU
backends the resident tier is the jnp mirror — same iteration fusion in one
XLA executable — and implicit geometries materialize their masked Gibbs
mirror on-device (the host still never ships an M*N operand); the table's
traffic formulas describe the TPU kernels. The cluster tier —
``repro.cluster``'s sharded lane pools — is the scheduler row times D
devices: per-device traffic is unchanged, the only cross-device bytes are
admission payloads to the owning shard. Problems too large for any lane
pool, or for one chip, run on the row-sharded gang,
``core.distributed.gang_solve`` / ``gang_solve_sharded``: each device runs
this module's streamed tier loop, ``streamed_solve``, on its row block,
so its traffic is the streamed column over M/D rows, plus O(N) allreduce
bytes per iteration.)

Traffic accounting: the table above is executable. ``repro.obs.traffic``
implements each cell as a formula function (``solve_bytes`` /
``chunk_bytes`` / ``cost_source_bytes`` / ``gang_collective_bytes``) and
the serving tiers charge a ``TrafficAccountant`` at every dispatch
decision — ``dispatch_observer()`` below exposes each ``impl='auto'``
routing with its (M, N, itemsize, num_iters) so per-solve bytes are
charged without re-deriving the routing. Charged ``T`` is the iteration
BUDGET (modeled upper bound): per-lane tol early exit happens on device
and is invisible to the host without extra syncs. tests/test_obs.py
asserts the accountant against this table cell by cell.

Measured performance: ``launch_profiler()`` below is the wall-clock twin
of ``dispatch_observer()`` — it times every routed solve/chunk launch
(to completion; installing it syncs each launch) keyed by the SAME table
parameters, so ``repro.obs.measure`` divides each cell's modeled bytes
by its measured seconds into achieved GB/s and a measured roofline
fraction, and ``dispatch_advisor()`` feeds those measurements back into
the 'auto' routing above. The single-device serving round no longer
installs it: its phases are profiler annotations, and a profiler trace
gives each chunk's device time by op without a sync.

bf16 storage on the resident tier upcasts once at load and downcasts once
at store, so the per-iteration bf16 rounding of the streamed path
disappears: resident bf16 iterates are the fp32 trajectory rounded once.

Two dispatch rows live OUTSIDE this table:

* the **log-domain escalation** path. Every tier above iterates in
  scaling space, which has a documented fp32 overflow regime
  (``core.sinkhorn_uv``: the mass-imbalance mode is a factor
  ``(Sa/Sb)**(rho/(2*eps))``). Problems classified into that regime by
  ``core.health.uv_safe`` — and lanes whose state goes non-finite in
  flight (``LaneState.healthy``) — are not retried here at all: the
  serving schedulers route them to ``core.sinkhorn_uot_log`` via
  ``core.health.escalate_log_solve``, whose potential-space iterates
  carry the same mode additively. That path trades the paper's HBM
  schedule for numerical range; it is the containment tier, not a
  performance tier.
* the **sliced 1-D degrade** path. Under overload
  (``shed_policy='degrade'`` + ``predictive=True``) point-cloud
  requests can leave the Sinkhorn family entirely: ``core.solve_1d``'s
  exact O((M+N) log(M+N)) 1-D solver, averaged over ``n_proj`` random
  projections by ``geometry.sliced`` — O(n_proj * (M+N)) memory, no
  M*N bytes or FLOPs anywhere, certified per-slice optimality gap on
  the label. Iteration-count feasibility for the rows above is judged
  *before* admission by ``core.predict`` (analytic contraction rate +
  online EWMA correction — the schedulers' service-time model). These
  are the accuracy-for-capacity tiers, not performance tiers.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.convergence import lane_factor_drift
from repro.core.problem import UOTConfig, rescale_factors
from repro.geometry import Geometry, PointCloudGeometry
from repro.geometry.pointcloud import _kernel_mirror, valid_mask
from repro.kernels import (uot_batched, uot_fused, uot_geometry,
                           uot_halfpass, uot_resident, uot_uv_fused)
from repro.kernels.vmem import VMEM_LIMIT_BYTES

_LANE = 128       # TPU lane width (minor dim alignment)
_SUBLANE = 8      # fp32 sublane count (16 for bf16 — see sublane_for)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret_default(interpret):
    return (not on_tpu()) if interpret is None else interpret


def _sublane(itemsize: int) -> int:
    return 2 * _SUBLANE if itemsize < 4 else _SUBLANE


def sublane_for(dtype) -> int:
    """Minor-2 dim alignment: 8 rows fp32, 16 rows for 2-byte types."""
    return _sublane(jnp.dtype(dtype).itemsize)


def _storage(cfg: UOTConfig, storage_dtype):
    return jnp.dtype(storage_dtype if storage_dtype is not None else cfg.dtype)


def _vector_bytes(rows: int, cols: int, count: int) -> int:
    """VMEM of ``count`` (rows, 1) and ``count`` (1, cols) fp32 operands:
    Mosaic pads a (rows, 1) block to the 128-lane width and a (1, cols)
    block to 8 sublanes, so an O(M) vector costs 512 bytes per row."""
    return count * 4 * (rows * _LANE + cols * _SUBLANE)


def streamed_vmem_bytes(block_m: int, N: int, itemsize: int = 4,
                        acc_itemsize: int = 4, *,
                        implicit: bool = False) -> int:
    """Scoped VMEM one grid step of the streamed kernels asks Mosaic for.

    The pipeline double-buffers the in and out ``(block_m, N)`` tiles in
    the storage dtype, and the kernel body holds one ``acc_itemsize``
    working copy of the tile, plus, for a storage dtype narrower than
    ``acc``, the tile cast back to storage before its store:
    ``block_m * N * (4*s + acc)``, ``+ s`` when ``s < acc`` (measured on
    the v5e compiler: 50.0 MiB for the fp32 frow kernel at
    (128, 20480) and at (32, 81920), 35.2 MiB for bf16 at (128, 20480)).
    On top come the O(block_m + N) factor, marginal and column-sum
    blocks. The implicit-geometry kernels (``uot_geometry``) load no input
    tile, but Mosaic keeps about ten ``acc_itemsize`` temporaries of the
    cost-tile arithmetic and the whole double-buffered ``(N, d)`` column
    cloud (one lane tile wide for d <= 128): ``block_m * N * (2*s +
    10*acc)`` plus the cloud, about twice the dense tier's bytes per row.
    Checked against the v5e compiler in tests/test_tpu_compile.py.
    """
    if implicit:
        per_elt = 2 * itemsize + 10 * acc_itemsize
        cloud = _vector_bytes(N, 0, 2)
    else:
        per_elt = 4 * itemsize + acc_itemsize + (
            itemsize if itemsize < acc_itemsize else 0)
        cloud = 0
    return block_m * N * per_elt + _vector_bytes(block_m, N, 4) + cloud


def resident_vmem_bytes(Mp: int, Np: int, itemsize: int = 4,
                        acc_itemsize: int = 4, *,
                        implicit: bool = False) -> int:
    """Scoped VMEM one lane of the resident kernels asks Mosaic for.

    Per lane (one grid step): the whole ``(Mp, Np)`` in and out tiles in
    the storage dtype, double-buffered by the pipeline, plus two
    ``acc_itemsize`` copies — the working tile carried through the
    iteration loop and the rescale temporary — and the O(Mp + Np)
    marginal, factor and column-sum vectors (the stepped kernel carries
    the most of them: about 14 of each, in and out, double-buffered,
    and their in-loop copies). ``implicit`` (``resident_solve_pc``)
    drops the input tile and adds the double-buffered coordinate blocks
    (one lane tile wide for d <= 128).
    """
    tiles = (2 if implicit else 4) * itemsize + 2 * acc_itemsize
    total = Mp * Np * tiles + _vector_bytes(Mp, Np, 14)
    if implicit:
        total += _vector_bytes(Mp + Np, 0, 2)
    return total


def pick_block_m(M: int, N: int, itemsize: int = 4,
                 acc_itemsize: int = 4, *, implicit: bool = False) -> int:
    """Largest power-of-two row block whose VMEM working set fits the budget.

    The working set per grid step is ``streamed_vmem_bytes``: the in + out
    tiles in the storage dtype (``itemsize`` bytes/elt, double-buffered by
    the pipeline), the fp32 compute copy of the tile (``acc_itemsize``)
    and the vector blocks. Mixed precision (bf16 storage) therefore earns
    a larger block than fp32 at the same budget. The block is also
    clamped to not exceed the (padded) problem height — no point padding
    M past the next power of two. A row too wide for even the sublane
    floor to fit gets the floor; such widths belong to ``solve_halfpass``.
    """
    sub = _sublane(itemsize)
    bm = 512
    while bm > sub and (
            streamed_vmem_bytes(bm, N, itemsize, acc_itemsize,
                                implicit=implicit) > VMEM_LIMIT_BYTES
            or bm >= 2 * M):
        bm //= 2
    return max(bm, sub)


def resident_fits(M: int, N: int, cfg: UOTConfig, *, storage_dtype=None,
                  budget_bytes: int | None = None,
                  implicit: bool = False) -> bool:
    """Whether a (M, N) problem can run on the VMEM-resident solver tier.

    The shape fits when ``resident_vmem_bytes`` of its padded tile — what
    Mosaic allocates for one lane of ``uot_resident.resident_solve`` /
    ``resident_stepped`` — is within the same budget ``pick_block_m``
    uses for the streamed tier, the scoped-VMEM limit every kernel
    passes to the compiler.

    ``implicit=True`` is the budget of the implicit-geometry kernel
    (``resident_solve_pc``): the cost operand is O((M + N) * d)
    coordinates computed into the working tile on-chip, so there is **no
    input tile** — the M*N-sized VMEM residents shrink to the coupling
    alone (double-buffered out tile + fp32 working copy + rescale
    temporary). At fp32 that is 16 bytes/element against the dense
    tier's 24, which is what lets ``impl='auto'`` route shapes to the
    resident tier under an implicit geometry that the dense path must
    stream (e.g. 1024x2560 fp32).

    The test is static (shapes, dtypes, budget), so ``impl='auto'``
    dispatch is decidable at trace time and batch size does not matter:
    the lane grid is sequential, one tile resident at a time.
    """
    sdt = _storage(cfg, storage_dtype)
    sub = _sublane(sdt.itemsize)
    Mp = M + (-M) % sub
    Np = N + (-N) % _LANE
    budget = VMEM_LIMIT_BYTES if budget_bytes is None else budget_bytes
    return resident_vmem_bytes(Mp, Np, sdt.itemsize,
                               implicit=implicit) <= budget


# ``impl='auto'`` routing decisions, observable so the dispatch boundary is
# assertable in tests and visible in benchmarks. Only 'auto' counts — an
# explicit impl is the caller's decision, not the dispatcher's.
#
# Counters are *per-context*: the historical module-global dict is only the
# base of a contextvar-held stack, and ``dispatch_counters()`` pushes a fresh
# dict for the dynamic extent of a ``with`` block. Every decision increments
# every dict on the stack (outer scopes aggregate inner activity), and
# ``dispatch_stats()`` / ``reset_dispatch_stats()`` address the *innermost*
# scope — so two schedulers (or two tests) observing their own dispatch
# decisions no longer clobber each other's counts, and contextvars give each
# thread / asyncio task its own stack on top of the shared global base.
_DISPATCH_GLOBAL = {"resident": 0, "streamed": 0}
_DISPATCH_CTX: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "uot_dispatch_counters", default=(_DISPATCH_GLOBAL,))


@contextlib.contextmanager
def dispatch_counters():
    """Isolated ``impl='auto'`` decision counters for a ``with`` block.

    Yields a ``{'resident': 0, 'streamed': 0}`` dict that counts only the
    decisions made inside the block (in this thread/task); enclosing scopes
    — including the process-global base that ``dispatch_stats()`` reports
    outside any block — keep counting too.
    """
    counters = {"resident": 0, "streamed": 0}
    token = _DISPATCH_CTX.set(_DISPATCH_CTX.get() + (counters,))
    try:
        yield counters
    finally:
        _DISPATCH_CTX.reset(token)


def _count_dispatch(kind: str) -> None:
    for counters in _DISPATCH_CTX.get():
        counters[kind] += 1


# Dispatch *observers* ride the same contextvar-stack idiom as the
# counters, but receive the full decision context — enough to charge the
# docstring's per-solve traffic formulas without re-deriving the routing
# (repro.obs.TrafficAccountant is the intended subscriber).
_DISPATCH_OBS: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "uot_dispatch_observers", default=())


@contextlib.contextmanager
def dispatch_observer(cb):
    """Subscribe ``cb(kind, M=, N=, itemsize=, num_iters=, implicit=)`` to
    every ``impl='auto'``/``'resident'`` routing decision made in the
    dynamic extent of the ``with`` block (this thread/task). ``kind`` is
    ``'resident'`` or ``'streamed'``; ``itemsize`` is the resolved storage
    dtype's; ``num_iters`` is the config's iteration budget (the modeled
    ``T`` — per-lane tol early exit is a device-side fact the host does
    not see). Observers stack: enclosing scopes keep receiving inner
    decisions, like ``dispatch_counters``.
    """
    token = _DISPATCH_OBS.set(_DISPATCH_OBS.get() + (cb,))
    try:
        yield cb
    finally:
        _DISPATCH_OBS.reset(token)


# Kernel-launch profiling rides the same contextvar-stack idiom one layer
# deeper than the dispatch observers: where ``dispatch_observer`` sees the
# routing *decision*, ``launch_profiler`` times the routed *launch* itself
# (``repro.obs.profile.KernelProfiler`` is the intended subscriber — its
# cells are keyed by the same parameters the traffic formulas take, so
# measured seconds divide modeled bytes directly). Timing a launch forces
# a ``block_until_ready`` sync, so nothing is timed unless a profiler is
# actually installed — and ``launch_profiler`` refuses disabled/null
# profilers outright, keeping the ``obs=False`` path sync-free. The
# ``UOTScheduler`` round does not install it (its phases are profiler
# annotations, and the trace times its chunks on the device); the cluster
# scheduler's sync step mode still does.
_LAUNCH_PROF: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "uot_launch_profilers", default=())


@contextlib.contextmanager
def launch_profiler(profiler):
    """Install ``profiler.observe_launch(kernel=, M=, N=, itemsize=, impl=,
    source=, lanes=, iters=, seconds=)`` for every solve/chunk launch in
    the dynamic extent (this thread/task). ``impl`` is the resolved tier
    ('resident'/'streamed'); ``seconds`` is host wall time to completion
    (the launch is synced — do not install on a path whose async overlap
    you are measuring). A None or ``enabled=False`` profiler installs
    nothing. Profilers stack like the dispatch observers.
    """
    if profiler is None or not getattr(profiler, "enabled", False):
        yield profiler
        return
    token = _LAUNCH_PROF.set(_LAUNCH_PROF.get() + (profiler,))
    try:
        yield profiler
    finally:
        _LAUNCH_PROF.reset(token)


def _profiled(kernel, fn, *, M, N, itemsize, impl, source="dense",
              lanes=1, iters=1):
    """Run ``fn()``; when profilers are installed, time it to completion
    and feed every installed profiler the measurement cell."""
    profs = _LAUNCH_PROF.get()
    if not profs:
        return fn()
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    dt = time.perf_counter() - t0
    for p in profs:
        p.observe_launch(kernel=kernel, M=M, N=N, itemsize=itemsize,
                         impl=impl, source=source, lanes=lanes, iters=iters,
                         seconds=dt)
    return out


# Measurement-driven dispatch: ``impl='auto'`` consults installed advisors
# (``repro.obs.measure.MeasuredDispatch`` over a persisted measurement
# store) BEFORE falling back to the static ``resident_fits`` budget.
# Advice is only taken where the static semantics already allow resident
# (the VMEM budget and the sub-fp32 stepped exclusion are correctness
# constraints, not tunables) — so an advisor can flip a resident-eligible
# shape to streamed when measurements say streaming is faster, never the
# reverse past the budget.
_DISPATCH_ADVISORS: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "uot_dispatch_advisors", default=())


@contextlib.contextmanager
def dispatch_advisor(advisor):
    """Install ``advisor.advise(M=, N=, itemsize=, implicit=, stepped=)
    -> 'resident' | 'streamed' | None`` for ``impl='auto'`` resolutions in
    the dynamic extent (this thread/task). The innermost advisor with an
    opinion (non-None) wins; None defers to the static budget."""
    token = _DISPATCH_ADVISORS.set(_DISPATCH_ADVISORS.get() + (advisor,))
    try:
        yield advisor
    finally:
        _DISPATCH_ADVISORS.reset(token)


def dispatch_stats() -> dict:
    """{'resident': ..., 'streamed': ...} decisions made by ``impl='auto'``
    in the innermost active ``dispatch_counters()`` scope (the process-wide
    totals when no scope is active)."""
    return dict(_DISPATCH_CTX.get()[-1])


def reset_dispatch_stats() -> None:
    """Zero the innermost active scope's counters (the process-wide totals
    when no ``dispatch_counters()`` scope is active)."""
    _DISPATCH_CTX.get()[-1].update(resident=0, streamed=0)


def pad_to(x: jax.Array, m_mult: int, n_mult: int) -> jax.Array:
    """Zero-pad the last two dims to multiples (works for 2-D and 3-D)."""
    M, N = x.shape[-2:]
    pm = (-M) % m_mult
    pn = (-N) % n_mult
    if pm or pn:
        pad = [(0, 0)] * (x.ndim - 2) + [(0, pm), (0, pn)]
        x = jnp.pad(x, pad)
    return x


def pad_vec(x: jax.Array, mult: int) -> jax.Array:
    """Zero-pad the last dim to a multiple (works for (M,) and (B, M))."""
    p = (-x.shape[-1]) % mult
    if not p:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, p)]
    return jnp.pad(x, pad)


def solve_fused(A0: jax.Array, a: jax.Array, b: jax.Array, cfg: UOTConfig,
                *, block_m: int | None = None, interpret: bool | None = None,
                storage_dtype=None, impl: str | None = None, geometry=None):
    """MAP-UOT solve built entirely from the fused Pallas kernel.

    Matches core.sinkhorn_uot_fused iterates (asserted in tests). Inputs of
    arbitrary shape; zero-padded internally to (block_m, 128) multiples.
    ``storage_dtype`` (default ``cfg.dtype``) sets the in-HBM dtype of the
    coupling matrix; accumulation/factors stay fp32.

    ``impl``: None/'kernel' runs the streamed per-iteration kernel loop
    (this function's historical behavior, fixed ``cfg.num_iters``);
    'resident' runs the whole solve VMEM-resident (one HBM read + write of
    the coupling for the entire solve, and — unlike the streamed path here
    — honoring ``cfg.tol`` early exit); 'auto' picks by ``resident_fits``.

    ``geometry=`` (exclusive with ``A0``) sources the initial coupling
    from a ``repro.geometry.Geometry``: ``A0 = K = exp(-C / reg)``. The
    solve is routed through the batched core at B=1, so — like 'auto' —
    it has ``cfg.tol`` per-lane early-exit semantics, and every ``impl``
    (including the default and 'jnp') is accepted. Implicit geometries
    never materialize an M*N cost array in HBM on the kernel path.
    """
    if geometry is not None:
        if A0 is not None:
            raise ValueError("pass either A0 or geometry=, not both")
        g = (_pc_batched(geometry)
             if isinstance(geometry, PointCloudGeometry) else geometry)
        P, colsum = solve_fused_batched(
            None, a[None], b[None], cfg, block_m=block_m,
            interpret=interpret, storage_dtype=storage_dtype, impl=impl,
            geometry=g)
        return P[0], colsum[0]
    if impl not in (None, "kernel", "auto", "resident"):
        raise ValueError(
            f"solve_fused impl must be None, 'kernel', 'auto' or 'resident',"
            f" got {impl!r} (for the vectorized XLA path use the core jnp"
            f" solvers or solve_fused_batched)")
    if impl in ("auto", "resident"):
        M, N = A0.shape
        if _resolve_auto(impl, M, N, cfg, storage_dtype):
            P, colsum, _, _ = solve_fused_resident(
                A0, a, b, cfg, interpret=interpret,
                storage_dtype=storage_dtype)
            return P, colsum
        # Over budget: stream via the batched path at B=1 rather than the
        # legacy fixed-iteration loop below, so 'auto' keeps tol semantics
        # (per-lane early exit) consistent across the dispatch boundary —
        # results must differ by tier in *traffic*, never in math.
        return _profiled(
            "solve", lambda: _solve_fused_one_streamed(
                A0, a, b, cfg, block_m=block_m, interpret=interpret,
                storage_dtype=storage_dtype),
            M=M, N=N, itemsize=_storage(cfg, storage_dtype).itemsize,
            impl="streamed", iters=cfg.num_iters)
    return _solve_fused_streamed(A0, a, b, cfg, block_m=block_m,
                                 interpret=interpret,
                                 storage_dtype=storage_dtype)


@functools.partial(jax.jit, static_argnames=("cfg", "block_m", "interpret",
                                             "storage_dtype"))
def _solve_fused_streamed(A0: jax.Array, a: jax.Array, b: jax.Array,
                          cfg: UOTConfig, *, block_m: int | None = None,
                          interpret: bool | None = None, storage_dtype=None):
    interpret = _interpret_default(interpret)
    M, N = A0.shape
    sdt = _storage(cfg, storage_dtype)
    bm = block_m or pick_block_m(M, N, sdt.itemsize)
    Ap = pad_to(A0.astype(sdt), bm, _LANE)
    ap = pad_vec(a, bm)
    bp = pad_vec(b, _LANE)
    fi = cfg.fi

    colsum = uot_fused.colsum(Ap, block_m=bm, interpret=interpret)

    def body(_, carry):
        A, colsum = carry
        fcol = rescale_factors(bp, colsum, fi)
        A, colsum = uot_fused.fused_iteration(
            A, fcol, ap, fi=fi, block_m=bm, interpret=interpret)
        return A, colsum

    Ap, colsum = jax.lax.fori_loop(0, cfg.num_iters, body, (Ap, colsum))
    return Ap[:M, :N], colsum[:N]


def _impl_default(impl, interpret):
    """'kernel' (Pallas) on TPU; vectorized 'jnp' elsewhere.

    Interpret-mode pallas emulation scans the grid carrying the WHOLE stack
    through a while_loop with full-buffer dynamic updates per grid step —
    O(grid * B*M*N) traffic — so it is for validation, not speed. Tests pin
    ``impl='kernel', interpret=True`` to exercise the real kernel schedule.

    'auto' and 'resident' pass through — the public wrappers resolve them
    to a tier (see ``resident_fits``) before reaching the jitted streamed
    cores, which only ever see 'kernel' or 'jnp'.
    """
    if impl is None:
        return "kernel" if (on_tpu() and not interpret) else "jnp"
    if impl not in ("kernel", "jnp", "auto", "resident"):
        raise ValueError(f"impl must be 'kernel', 'jnp', 'auto' or "
                         f"'resident', got {impl!r}")
    return impl


def _resolve_auto(impl, M, N, cfg, storage_dtype, *, stepped_sdt=None,
                  implicit=False):
    """Resolve 'auto'/'resident' to a tier for a (M, N) problem.

    Returns True to route resident. For the stepped path pass the pool's
    storage dtype as ``stepped_sdt``: sub-fp32 pools never auto-route
    resident, because the resident chunk rounds the tile once per chunk
    instead of once per iteration, which would make a bf16 lane's iterates
    depend on chunk boundaries (the streamed stepped path guarantees
    chunk-boundary invariance; see ``uot_resident.resident_stepped``).
    ``implicit`` selects the implicit-geometry VMEM budget (no input tile
    — see ``resident_fits``), widening the resident shape range.

    With a ``dispatch_advisor`` installed, a resident-eligible 'auto'
    resolution asks it first — measured tier costs override the static
    budget's guess where a measurement cell has data (None defers).
    """
    fits = resident_fits(M, N, cfg, storage_dtype=storage_dtype,
                         implicit=implicit)
    if impl == "resident":
        if not fits:
            raise ValueError(
                f"({M}, {N}) exceeds the resident VMEM budget; use "
                f"impl='auto' to fall back to the streamed tier")
        return True
    s = _storage(cfg, stepped_sdt if stepped_sdt is not None
                 else storage_dtype).itemsize
    resident = fits and not (stepped_sdt is not None
                             and jnp.dtype(stepped_sdt).itemsize < 4)
    if resident:
        for adv in reversed(_DISPATCH_ADVISORS.get()):
            choice = adv.advise(M=M, N=N, itemsize=s, implicit=implicit,
                                stepped=stepped_sdt is not None)
            if choice in ("resident", "streamed"):
                resident = choice == "resident"
                break
    kind = "resident" if resident else "streamed"
    _count_dispatch(kind)
    for cb in _DISPATCH_OBS.get():
        cb(kind, M=M, N=N, itemsize=s, num_iters=cfg.num_iters,
           implicit=implicit)
    return resident


def _stepped_iter(A, colsum, upd, *, ap, bp, fi, sdt, impl, bm, interpret,
                  in_place=True, axis=None):
    """One (optionally masked) batched Algorithm-1 iteration on padded state.

    ``upd`` is a (B,) bool lane mask or None. With ``upd=None`` every lane
    is updated and the row factors are not materialized on the kernel path
    (the lean fixed-iteration path). With a mask, lanes where ``upd`` is
    False keep their (A, colsum) bit-for-bit — per-lane math is
    independent, so a frozen lane's iterate is exactly the one it had when
    its flag fired. Freezing is free of extra M*N traffic: the jnp path
    masks the two rescale *factors* to exactly 1.0 (a multiplicative
    no-op, so no full-size select materializes), and the kernel path
    selects input-vs-result per tile while it is already in VMEM. Only the
    O(B*N) colsum keeps an explicit select, pinning the carried-colsum
    value under bf16 storage (recomputing it from a stored bf16 tile would
    drift by a rounding, making results chunk-boundary-dependent).

    The kernels write A' over A's buffer; ``in_place=False`` (masked
    kernel path only) writes a new one, for a first iteration whose A is
    the caller's (see ``batched_fused_iteration_frow``).

    With ``axis`` (a mesh axis name) A is one device's row block and the
    new column sums are summed over the axis before anything reads them.

    Returns (A', colsum', frow) where frow (B, M) are this iteration's
    *computed* row factors even for frozen lanes (None on the unmasked
    kernel path); the caller turns successive frows into the per-lane
    stationarity drift via ``lane_factor_drift`` and masks what it carries.
    """
    fcol = rescale_factors(bp, colsum, fi)
    if impl == "jnp":
        fcol_m = (fcol if upd is None
                  else jnp.where(upd[:, None], fcol, 1.0))
        blk = A.astype(jnp.float32) * fcol_m[:, None, :]
        rowsum = blk.sum(axis=2)
        frow = rescale_factors(ap, rowsum, fi)
        frow_m = (frow if upd is None
                  else jnp.where(upd[:, None], frow, 1.0))
        blk = blk * frow_m[:, :, None]
        newA, newcs = blk.astype(sdt), blk.sum(axis=1)
    elif upd is None:
        newA, newcs = uot_batched.batched_fused_iteration(
            A, fcol, ap, fi=fi, block_m=bm, interpret=interpret)
        frow = None
    else:
        newA, newcs, frow = uot_batched.batched_fused_iteration_frow(
            A, fcol, ap, upd, fi=fi, block_m=bm, interpret=interpret,
            in_place=in_place)
    if axis is not None:
        newcs = jax.lax.psum(newcs, axis)
    if upd is None:
        return newA, newcs, frow
    colsum = jnp.where(upd[:, None], newcs, colsum)
    return newA, colsum, frow


# ---- implicit-geometry plumbing -------------------------------------------

def _pc_batched(g: PointCloudGeometry) -> PointCloudGeometry:
    """Lift a single-problem point-cloud geometry to a batch of one."""
    if g.batch_shape:
        return g
    return dataclasses.replace(
        g, x=g.x[None], y=g.y[None], xn=g.xn[None], yn=g.yn[None],
        m_valid=None if g.m_valid is None else jnp.reshape(g.m_valid, (1,)),
        n_valid=None if g.n_valid is None else jnp.reshape(g.n_valid, (1,)))


def _pc_padded_operands(g: PointCloudGeometry, Mp: int, Np: int):
    """Zero-pad the coordinate operands to kernel-aligned (Mp, Np); returns
    (x, xn, y, yn, m_valid, n_valid) ready for the pc kernels.

    Padded coordinate rows are zeros; it is the kernels' validity mask
    (not the coordinate values) that makes the padded region of every
    computed tile exactly 0.0, mirroring a zero-padded dense stack.
    """
    B, M, _ = g.x.shape
    N = g.y.shape[1]
    x = jnp.pad(g.x, ((0, 0), (0, Mp - M), (0, 0)))
    xn = jnp.pad(g.xn, ((0, 0), (0, Mp - M)))
    y = jnp.pad(g.y, ((0, 0), (0, Np - N), (0, 0)))
    yn = jnp.pad(g.yn, ((0, 0), (0, Np - N)))
    mv = (jnp.full((B,), M, jnp.int32) if g.m_valid is None
          else g.m_valid.astype(jnp.int32))
    nv = (jnp.full((B,), N, jnp.int32) if g.n_valid is None
          else g.n_valid.astype(jnp.int32))
    return x, xn, y, yn, mv, nv


@functools.partial(jax.jit, static_argnames=("cfg", "block_m", "interpret",
                                             "storage_dtype"))
def _solve_fused_batched_geometry_streamed(geom: PointCloudGeometry,
                                           a: jax.Array, b: jax.Array,
                                           cfg: UOTConfig, *,
                                           block_m: int | None = None,
                                           interpret: bool | None = None,
                                           storage_dtype=None):
    """Streamed batched solve with the Gibbs kernel computed on-chip.

    The implicit twin of ``_solve_fused_batched_streamed``'s 'kernel'
    path: Algorithm 1's preprocessing colsum and first iteration evaluate
    cost tiles in VMEM from the geometry's coordinates
    (``uot_geometry.batched_pc_*``) — the initial coupling never exists in
    HBM; the solve's first M*N write is the already-rescaled ``A1``. From
    iteration 2 the coupling is ordinary solver state and the standard
    streamed kernels take over, with identical tol bookkeeping
    (first-iteration drift vs unit factors). Where the same row block
    fits both paths (everywhere but near the VMEM budget, where the
    tile-compute kernels need the smaller block) the blocking is
    identical too, and in interpret mode the iterates match the
    dense-load path bit-for-bit.
    """
    interpret = _interpret_default(interpret)
    M, N = geom.shape
    sdt = _storage(cfg, storage_dtype)
    bm = block_m or pick_block_m(M, N, sdt.itemsize, implicit=True)
    Mp = M + (-M) % bm
    Np = N + (-N) % _LANE
    x, xn, y, yn, mv, nv = _pc_padded_operands(geom, Mp, Np)
    ap = pad_vec(a, bm)
    bp = pad_vec(b, _LANE)
    fi = cfg.fi
    reg, scale = float(cfg.reg), geom.scale

    colsum0 = uot_geometry.batched_pc_colsum(
        x, xn, y, yn, mv, nv, reg=reg, scale=scale, block_m=bm,
        interpret=interpret, storage_dtype=sdt)
    if cfg.num_iters == 0:
        A = uot_geometry.batched_pc_materialize(
            x, xn, y, yn, mv, nv, reg=reg, scale=scale, block_m=bm,
            interpret=interpret, out_dtype=sdt)
        return A[:, :M, :N], colsum0[:, :N]

    fcol = rescale_factors(bp, colsum0, fi)
    Ap, colsum, frow1 = uot_geometry.batched_pc_first_iteration(
        fcol, ap, x, xn, y, yn, mv, nv, fi=fi, reg=reg, scale=scale,
        block_m=bm, interpret=interpret, out_dtype=sdt)

    it = functools.partial(_stepped_iter, ap=ap, bp=bp, fi=fi, sdt=sdt,
                           impl="kernel", bm=bm, interpret=interpret)
    if cfg.tol is None:
        def body(_, carry):
            A, colsum = carry
            A, colsum, _ = it(A, colsum, None)
            return A, colsum
        Ap, colsum = jax.lax.fori_loop(1, cfg.num_iters, body, (Ap, colsum))
    else:
        # same bookkeeping as the dense while_loop's first pass: drift of
        # the first row factors against the all-ones prior
        drift1 = lane_factor_drift(frow1, jnp.ones_like(ap))
        conv1 = drift1 <= cfg.tol

        def cond(carry):
            _, _, _, conv, i = carry
            return jnp.logical_and(i < cfg.num_iters, ~jnp.all(conv))

        def wbody(carry):
            A, colsum, prev_frow, conv, i = carry
            upd = ~conv
            A, colsum, frow = it(A, colsum, upd)
            drift = lane_factor_drift(frow, prev_frow)
            prev_frow = jnp.where(upd[:, None], frow, prev_frow)
            return A, colsum, prev_frow, conv | (drift <= cfg.tol), i + 1

        Ap, colsum, _, _, _ = jax.lax.while_loop(
            cond, wbody, (Ap, colsum, frow1, conv1, jnp.int32(1)))
    return Ap[:, :M, :N], colsum[:, :N]


def _solve_fused_batched_geometry(geom, a, b, cfg, *, block_m=None,
                                  interpret=None, storage_dtype=None,
                                  impl=None):
    """Dispatch a batched geometry solve to a tier + flavor.

    Implicit point-cloud geometries route between the tile-compute
    streamed kernels, the implicit resident kernel (with the widened
    ``resident_fits(implicit=True)`` budget) and the jnp mirror (which
    materializes the masked Gibbs stack on-device — the host still never
    ships an M*N operand). Explicit/materializable geometries (dense,
    grid) materialize their Gibbs mirror once and take the ordinary dense
    path unchanged.
    """
    if not isinstance(geom, Geometry):
        raise TypeError(f"geometry= expects a repro.geometry.Geometry, "
                        f"got {type(geom).__name__}")
    B = a.shape[0]
    if not isinstance(geom, PointCloudGeometry):
        A0 = geom.kernel(cfg.reg)
        if A0.ndim == 2:
            A0 = jnp.broadcast_to(A0, (B,) + A0.shape)
        return solve_fused_batched(A0, a, b, cfg, block_m=block_m,
                                   interpret=interpret,
                                   storage_dtype=storage_dtype, impl=impl)
    geom = _pc_batched(geom)
    if geom.x.shape[0] != B:
        raise ValueError(f"geometry batch {geom.x.shape[0]} != marginal "
                         f"batch {B}")
    interp = _interpret_default(interpret)
    impl = _impl_default(impl, interp)
    M, N = geom.shape
    s = _storage(cfg, storage_dtype).itemsize
    if impl in ("auto", "resident"):
        if _resolve_auto(impl, M, N, cfg, storage_dtype, implicit=True):
            P, colsum, _, _ = _profiled(
                "solve", lambda: solve_fused_resident(
                    None, a, b, cfg, interpret=interpret,
                    storage_dtype=storage_dtype, geometry=geom),
                M=M, N=N, itemsize=s, impl="resident", source="implicit",
                lanes=B, iters=cfg.num_iters)
            return P, colsum
        impl = _impl_default(None, interp)  # over budget: streamed default
    if impl == "jnp":
        A0 = geom.kernel(cfg.reg)
        return _profiled(
            "solve", lambda: _solve_fused_batched_streamed(
                A0, a, b, cfg, block_m=block_m, interpret=interpret,
                storage_dtype=storage_dtype, impl="jnp"),
            M=M, N=N, itemsize=s, impl="streamed", source="implicit",
            lanes=B, iters=cfg.num_iters)
    return _profiled(
        "solve", lambda: _solve_fused_batched_geometry_streamed(
            geom, a, b, cfg, block_m=block_m, interpret=interpret,
            storage_dtype=storage_dtype),
        M=M, N=N, itemsize=s, impl="streamed", source="implicit",
        lanes=B, iters=cfg.num_iters)


def solve_fused_batched(A0: jax.Array, a: jax.Array, b: jax.Array,
                        cfg: UOTConfig, *, block_m: int | None = None,
                        interpret: bool | None = None, storage_dtype=None,
                        impl: str | None = None, geometry=None):
    """MAP-UOT solve for a stack of same-shape problems in one launch.

    A0: (B, M, N); a: (B, M); b: (B, N). On TPU (``impl='kernel'``) one
    ``(batch, row_blocks)``-grid pallas_call per iteration covers the whole
    stack — one dispatch instead of B, with each problem keeping the
    read+write-once schedule and its own (1, N) column-sum accumulator.
    ``impl='jnp'`` (the non-TPU default) runs the identical padded
    iteration math vectorized over the batch in XLA. ``impl='resident'``
    runs the whole solve on the VMEM-resident tier (one read + one write of
    each coupling for the entire solve; bf16 storage is rounded once at
    the end instead of every iteration); ``impl='auto'`` picks the tier by
    ``resident_fits``. Returns (P, colsum) of shapes (B, M, N) and (B, N).

    With ``cfg.tol`` set the solve early-exits per lane: a lane whose
    row-factor stationarity ``max|frow_t - frow_{t-1}|`` (the same
    criterion as the single-problem solvers — see ``sinkhorn_baseline`` on
    why not ``|f - 1|``) falls to ``tol`` is frozen (masked out of further
    updates on the streamed tier; stops computing on the resident tier) at
    exactly that iterate, and the loop ends once every lane has converged
    or ``num_iters`` is hit — fixed-shape batches stop dragging
    already-converged problems to the iteration cap.

    ``geometry=`` (exclusive with ``A0``) sources the initial coupling
    from a ``repro.geometry.Geometry`` instead of a dense stack: the
    Gibbs kernel ``K = exp(-C / reg)`` becomes ``A0``. For implicit
    geometries (``PointCloudGeometry``, batched coordinates + optional
    per-problem valid counts) the 'kernel' path computes cost tiles
    on-chip and never materializes an M*N cost array in HBM, and
    ``impl='auto'`` uses the widened implicit resident budget (see
    ``resident_fits``); couplings match the dense-load path bit-for-bit
    in fp32.
    """
    if geometry is not None:
        if A0 is not None:
            raise ValueError("pass either A0 or geometry=, not both")
        return _solve_fused_batched_geometry(
            geometry, a, b, cfg, block_m=block_m, interpret=interpret,
            storage_dtype=storage_dtype, impl=impl)
    impl = _impl_default(impl, _interpret_default(interpret))
    B, M, N = A0.shape
    s = _storage(cfg, storage_dtype).itemsize
    if impl in ("auto", "resident"):
        if _resolve_auto(impl, M, N, cfg, storage_dtype):
            P, colsum, _, _ = _profiled(
                "solve", lambda: solve_fused_resident(
                    A0, a, b, cfg, interpret=interpret,
                    storage_dtype=storage_dtype),
                M=M, N=N, itemsize=s, impl="resident", lanes=B,
                iters=cfg.num_iters)
            return P, colsum
        impl = None  # over budget: fall through to the streamed default
    return _profiled(
        "solve", lambda: _solve_fused_batched_streamed(
            A0, a, b, cfg, block_m=block_m, interpret=interpret,
            storage_dtype=storage_dtype, impl=impl),
        M=M, N=N, itemsize=s, impl="streamed", lanes=B,
        iters=cfg.num_iters)


@functools.partial(jax.jit, static_argnames=("cfg", "block_m", "interpret",
                                             "storage_dtype", "impl"))
def _solve_fused_batched_streamed(A0: jax.Array, a: jax.Array, b: jax.Array,
                                  cfg: UOTConfig, *,
                                  block_m: int | None = None,
                                  interpret: bool | None = None,
                                  storage_dtype=None,
                                  impl: str | None = None):
    P, colsum, _ = streamed_solve(A0, a, b, cfg, block_m=block_m,
                                  interpret=interpret,
                                  storage_dtype=storage_dtype, impl=impl)
    return P, colsum


def streamed_solve(A0: jax.Array, a: jax.Array, b: jax.Array,
                   cfg: UOTConfig, *, block_m: int | None = None,
                   interpret: bool | None = None, storage_dtype=None,
                   impl: str | None = None, axis: str | None = None):
    """The streamed tier's solve loop over a (B, M, N) stack, unjitted.

    Returns ``(P, colsum, iters)``; ``iters`` is the number of passes the
    loop ran (with ``cfg.tol``, the iteration count of a batch of one).

    With ``axis`` (a mesh axis name, inside ``shard_map``) the stack is
    one device's row block of a larger problem, the row-sharded gang of
    ``core.distributed``: the column-sum partials are summed over the
    axis after the first pass and after every iteration (the paper's
    ``MPI_Allreduce``, 4*N bytes a lane), and each lane's row-factor
    drift is the largest over the axis, so every device leaves the loop
    on the same iteration with the same iterate as one device would.
    Without ``axis`` it is exactly the one-device loop.
    """
    interpret = _interpret_default(interpret)
    impl = _impl_default(impl, interpret)
    B, M, N = A0.shape
    sdt = _storage(cfg, storage_dtype)
    bm = block_m or pick_block_m(M, N, sdt.itemsize)
    Ap = pad_to(A0.astype(sdt), bm, _LANE)
    ap = pad_vec(a, bm)
    bp = pad_vec(b, _LANE)
    fi = cfg.fi

    if impl == "jnp":
        colsum = Ap.astype(jnp.float32).sum(axis=1)
    else:
        colsum = uot_batched.batched_colsum(
            Ap, block_m=bm, interpret=interpret)
    if axis is not None:
        colsum = jax.lax.psum(colsum, axis)

    it = functools.partial(_stepped_iter, ap=ap, bp=bp, fi=fi, sdt=sdt,
                           impl=impl, bm=bm, interpret=interpret, axis=axis)
    if cfg.tol is None:
        def body(_, carry):
            A, colsum = carry
            A, colsum, _ = it(A, colsum, None)
            return A, colsum
        Ap, colsum = jax.lax.fori_loop(0, cfg.num_iters, body, (Ap, colsum))
        iters = jnp.int32(cfg.num_iters)
    else:
        def cond(carry):
            _, _, _, conv, i = carry
            return jnp.logical_and(i < cfg.num_iters, ~jnp.all(conv))

        def wbody(carry, in_place=True):
            A, colsum, prev_frow, conv, i = carry
            upd = ~conv
            A, colsum, frow = it(A, colsum, upd, in_place=in_place)
            drift = lane_factor_drift(frow, prev_frow)
            if axis is not None:
                drift = jax.lax.pmax(drift, axis)
            prev_frow = jnp.where(upd[:, None], frow, prev_frow)
            return A, colsum, prev_frow, conv | (drift <= cfg.tol), i + 1

        carry = (Ap, colsum, jnp.ones_like(ap), jnp.zeros((B,), bool),
                 jnp.int32(0))
        if cfg.num_iters:
            # The loop's first pass, run before it into a new buffer: the
            # loop then owns the coupling it writes in place, and the
            # caller's A0 is never copied.
            carry = wbody(carry, in_place=False)
        Ap, colsum, _, _, iters = jax.lax.while_loop(cond, wbody, carry)
    return Ap[:, :M, :N], colsum[:, :N], iters


@functools.partial(jax.jit, static_argnames=("cfg", "block_m", "interpret",
                                             "storage_dtype"))
def _solve_fused_one_streamed(A0: jax.Array, a: jax.Array, b: jax.Array,
                              cfg: UOTConfig, *, block_m: int | None = None,
                              interpret: bool | None = None,
                              storage_dtype=None):
    """``_solve_fused_batched_streamed`` on one (M, N) problem, as a batch
    of one. The batch axis is put on and taken off inside this executable,
    where both are bitcasts; done eagerly, each is a copy of the coupling.
    """
    P, colsum = _solve_fused_batched_streamed(
        A0[None], a[None], b[None], cfg, block_m=block_m,
        interpret=interpret, storage_dtype=storage_dtype)
    return P[0], colsum[0]


def solve_fused_resident(A0: jax.Array, a: jax.Array, b: jax.Array,
                         cfg: UOTConfig, *, interpret: bool | None = None,
                         storage_dtype=None, impl: str | None = None,
                         geometry=None):
    """Whole-solve VMEM-resident MAP-UOT: load once, iterate, store once.

    A0 may be (M, N) or (B, M, N) (a/b matching). ``impl`` selects the
    flavor *within* the resident tier with the usual convention: 'kernel'
    is the Pallas lane-grid kernel (``uot_resident.resident_solve``; TPU
    default, interpretable on CPU for validation), 'jnp' (non-TPU default)
    is the same iteration fusion in one XLA executable. Both honor
    ``cfg.tol`` per lane with the streamed solvers' row-factor-stationarity
    criterion — same iterate, same iteration count.

    ``geometry=`` (exclusive with ``A0``) sources the tile from a
    ``Geometry``. Implicit point-cloud geometries run
    ``uot_resident.resident_solve_pc`` on the 'kernel' flavor — each
    lane's tile is COMPUTED in VMEM from its coordinates (per-solve
    coupling HBM traffic: write MN, no read) — and are budgeted with
    ``resident_fits(implicit=True)``, which admits shapes the dense tier
    must stream. The 'jnp' flavor materializes the Gibbs mirror on-device
    first (the host still never ships an M*N operand).

    Returns (P, colsum, iters, err); leading batch dims only if A0/the
    marginals had one. The extra per-lane outputs (iteration counts, final
    drift) come for free from the in-kernel convergence loop and are what
    the parity tests pin against the streamed tier.
    """
    interpret = _interpret_default(interpret)
    if impl not in (None, "kernel", "jnp"):
        raise ValueError(f"resident flavor must be None, 'kernel' or 'jnp', "
                         f"got {impl!r}")
    flavor = _impl_default(impl, interpret)
    if geometry is not None:
        if A0 is not None:
            raise ValueError("pass either A0 or geometry=, not both")
        return _solve_fused_resident_geometry(
            geometry, a, b, cfg, interpret=interpret,
            storage_dtype=storage_dtype, flavor=flavor)
    single = A0.ndim == 2
    if single:
        A0, a, b = A0[None], a[None], b[None]
    B, M, N = A0.shape
    if not resident_fits(M, N, cfg, storage_dtype=storage_dtype):
        # guard here too (not just in the impl='resident' dispatch routes)
        # so an over-budget shape gets this error instead of an opaque
        # Mosaic VMEM-exhaustion failure from the whole-tile BlockSpec
        raise ValueError(
            f"({M}, {N}) exceeds the resident VMEM budget; use "
            f"impl='auto' to fall back to the streamed tier")
    sdt = _storage(cfg, storage_dtype)
    sub = _sublane(sdt.itemsize)
    Ap = pad_to(A0.astype(sdt), sub, _LANE)
    ap = pad_vec(a.astype(jnp.float32), sub)
    bp = pad_vec(b.astype(jnp.float32), _LANE)
    if flavor == "kernel":
        P, colsum, iters, err = uot_resident.resident_solve(
            Ap, ap, bp, fi=cfg.fi, num_iters=cfg.num_iters, tol=cfg.tol,
            interpret=interpret)
    else:
        P, colsum, iters, err = uot_resident.resident_solve_jnp(
            Ap, ap, bp, fi=cfg.fi, num_iters=cfg.num_iters, tol=cfg.tol,
            out_dtype=sdt)
    P, colsum = P[:, :M, :N], colsum[:, :N]
    if single:
        return P[0], colsum[0], iters[0], err[0]
    return P, colsum, iters, err


def _solve_fused_resident_geometry(geom, a, b, cfg, *, interpret, flavor,
                                   storage_dtype=None):
    """Resident-tier solve with the tile sourced from a ``Geometry``."""
    if not isinstance(geom, Geometry):
        raise TypeError(f"geometry= expects a repro.geometry.Geometry, "
                        f"got {type(geom).__name__}")
    if not isinstance(geom, PointCloudGeometry):
        A0 = geom.kernel(cfg.reg)
        if A0.ndim == 2 and a.ndim == 2:
            A0 = jnp.broadcast_to(A0, (a.shape[0],) + A0.shape)
        return solve_fused_resident(A0, a, b, cfg, interpret=interpret,
                                    storage_dtype=storage_dtype,
                                    impl=flavor)
    single = a.ndim == 1
    if single:
        a, b = a[None], b[None]
    geom = _pc_batched(geom)
    B = a.shape[0]
    if geom.x.shape[0] != B:
        raise ValueError(f"geometry batch {geom.x.shape[0]} != marginal "
                         f"batch {B}")
    M, N = geom.shape
    if not resident_fits(M, N, cfg, storage_dtype=storage_dtype,
                         implicit=True):
        raise ValueError(
            f"({M}, {N}) exceeds the implicit resident VMEM budget; use "
            f"impl='auto' to fall back to the streamed tier")
    sdt = _storage(cfg, storage_dtype)
    sub = _sublane(sdt.itemsize)
    Mp = M + (-M) % sub
    Np = N + (-N) % _LANE
    ap = pad_vec(a.astype(jnp.float32), sub)
    bp = pad_vec(b.astype(jnp.float32), _LANE)
    if flavor == "kernel":
        x, xn, y, yn, mv, nv = _pc_padded_operands(geom, Mp, Np)
        P, colsum, iters, err = uot_resident.resident_solve_pc(
            x, xn, y, yn, ap, bp, mv, nv, fi=cfg.fi, reg=float(cfg.reg),
            scale=geom.scale, num_iters=cfg.num_iters, tol=cfg.tol,
            interpret=interpret, out_dtype=sdt)
    else:
        Ap = pad_to(geom.kernel(cfg.reg).astype(sdt), sub, _LANE)
        P, colsum, iters, err = uot_resident.resident_solve_jnp(
            Ap, ap, bp, fi=cfg.fi, num_iters=cfg.num_iters, tol=cfg.tol,
            out_dtype=sdt)
    P, colsum = P[:, :M, :N], colsum[:, :N]
    if single:
        return P[0], colsum[0], iters[0], err[0]
    return P, colsum, iters, err


# ---- steppable solving: explicit carried state for continuous batching ----

@dataclasses.dataclass
class LaneState:
    """Carried state of a fixed pool of batched solver lanes.

    A *lane* is one slot of a padded (L, Mp, Np) problem stack — the UOT
    analogue of an LLM serving slot. The pool is advanced a chunk of
    Algorithm-1 iterations at a time by ``solve_fused_stepped``; between
    chunks a host-side scheduler may ``lane_evict`` finished lanes and
    ``lane_admit`` queued problems into the freed slots, which is what makes
    continuous batching possible (admission never waits for the whole stack
    to finish). Free lanes hold all-zero problems — exact no-ops for the
    rescaling math — so a partially occupied pool computes the same answers
    as a dense one. Per-lane math is independent of pool occupancy, so a
    problem's trajectory is identical whatever lane it lands in and whatever
    shares the pool.

    Fields (all jax arrays; the dataclass is a registered pytree so it can
    be carried through jit/fori_loop):
      P:         (L, Mp, Np) coupling iterate, storage dtype (fp32 or bf16).
      colsum:    (L, Np) fp32 carried column sums (Algorithm 1's interweaved
                 accumulator, valid for the *next* column rescale).
      a, b:      (L, Mp) / (L, Np) fp32 marginals, zero-padded.
      frow:      (L, Mp) fp32 row rescale factors of the lane's previous
                 iteration (ones at admission) — successive frows give the
                 per-lane stationarity drift, the convergence criterion.
      iters:     (L,) int32 iterations each lane has run since admission.
      converged: (L,) bool — the lane's factor drift fell to ``cfg.tol``
                 (never set when ``cfg.tol`` is None).
      active:    (L,) bool — lane holds a live problem.
      healthy:   (L,) bool — the lane's iterates are numerically sound.
                 Cleared (latched False) by the stepped advance when the
                 lane's freshly computed row factors or carried column
                 sums go non-finite; an unhealthy lane is frozen exactly
                 like a converged one (its poison never multiplies back
                 into the pool) and reads as finished via ``lane_done``,
                 so a scheduler evicts it at the next chunk boundary.
                 Detection is traffic-free: the detector folds over the
                 O(L*(M+N)) frow/colsum values the convergence check
                 already holds — the M*N tile is never rescanned.
      m_valid:   (L,) int32 valid row count of each lane's problem (0 for a
                 free lane). Everything beyond it is exact zero padding.
      n_valid:   (L,) int32 valid column count, likewise.

    ``m_valid`` / ``n_valid`` are what let one *physical* pool host lanes of
    several padded shapes (cross-bucket lane sharing): zero-padding is an
    exact no-op for the rescaling math — padded rows/cols carry zero mass,
    get unit factors, and appended zeros are exact identities of every float
    reduction — so a lane admitted into a pool wider than its own bucket
    produces the bit-identical iterate on its valid region, and the counts
    record where that region ends without consulting host-side request
    metadata. ``lane_admit`` *enforces* the mask (zeroes everything beyond
    the counts) so a sloppy caller cannot leak payload into the padding.
    """

    P: jax.Array
    colsum: jax.Array
    a: jax.Array
    b: jax.Array
    frow: jax.Array
    iters: jax.Array
    converged: jax.Array
    active: jax.Array
    m_valid: jax.Array
    n_valid: jax.Array
    healthy: jax.Array

    @property
    def num_lanes(self) -> int:
        return self.P.shape[0]


jax.tree_util.register_dataclass(
    LaneState,
    data_fields=["P", "colsum", "a", "b", "frow", "iters", "converged",
                 "active", "m_valid", "n_valid", "healthy"],
    meta_fields=[])


def make_lane_state(num_lanes: int, M: int, N: int, cfg: UOTConfig, *,
                    block_m: int | None = None,
                    storage_dtype=None) -> LaneState:
    """Empty lane pool for problems of (padded) shape up to (M, N).

    The pool's internal shape is (M, N) rounded up to kernel alignment
    (row-block multiple, lane-width columns); admitted problems may be any
    shape that fits. One pool per shape bucket is the intended layout.
    """
    sdt = _storage(cfg, storage_dtype)
    bm = block_m or pick_block_m(M, N, sdt.itemsize)
    Mp = M + (-M) % bm
    Np = N + (-N) % _LANE
    L = num_lanes
    return LaneState(
        P=jnp.zeros((L, Mp, Np), sdt),
        colsum=jnp.zeros((L, Np), jnp.float32),
        a=jnp.zeros((L, Mp), jnp.float32),
        b=jnp.zeros((L, Np), jnp.float32),
        frow=jnp.ones((L, Mp), jnp.float32),
        iters=jnp.zeros((L,), jnp.int32),
        converged=jnp.zeros((L,), bool),
        active=jnp.zeros((L,), bool),
        m_valid=jnp.zeros((L,), jnp.int32),
        n_valid=jnp.zeros((L,), jnp.int32),
        healthy=jnp.ones((L,), bool))


def _pad_admit_payload(Mp: int, Np: int, K: jax.Array, a: jax.Array,
                       b: jax.Array, m_valid, n_valid, storage_dtype):
    """Zero-pad (and validity-mask) an admission payload to a pool shape.

    K (..., M, N), a (..., M), b (..., N); ``m_valid`` / ``n_valid`` are
    optional per-problem valid counts (int scalars or (...,) vectors,
    default: the payload's own M, N — i.e. the whole payload is live).
    Returns (Kp, ap, bp, mv, nv) padded to (Mp, Np) with everything beyond
    the valid counts forced to exactly 0.0 — the invariant cross-bucket
    lane sharing rests on. Shared by ``lane_admit`` and the cluster-tier
    admission (``repro.cluster``).
    """
    M, N = K.shape[-2:]
    lead = K.shape[:-2]
    mv = (jnp.full(lead, M, jnp.int32) if m_valid is None
          else jnp.broadcast_to(jnp.asarray(m_valid, jnp.int32), lead))
    nv = (jnp.full(lead, N, jnp.int32) if n_valid is None
          else jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), lead))
    Kp = jnp.zeros(lead + (Mp, Np), storage_dtype).at[..., :M, :N].set(
        K.astype(storage_dtype))
    ap = jnp.zeros(lead + (Mp,), jnp.float32).at[..., :M].set(
        a.astype(jnp.float32))
    bp = jnp.zeros(lead + (Np,), jnp.float32).at[..., :N].set(
        b.astype(jnp.float32))
    # enforce the mask: rows/cols beyond the per-problem valid counts are
    # exact zeros even if the caller's payload carried junk there (a no-op
    # — where(True, x, 0) is x — for the default whole-payload counts)
    rmask = jnp.arange(Mp) < mv[..., None]
    cmask = jnp.arange(Np) < nv[..., None]
    Kp = jnp.where(rmask[..., :, None] & cmask[..., None, :], Kp, 0)
    ap = jnp.where(rmask, ap, 0)
    bp = jnp.where(cmask, bp, 0)
    return Kp, ap, bp, mv, nv


@jax.jit
def lane_admit(state: LaneState, lane, K: jax.Array, a: jax.Array,
               b: jax.Array, m_valid=None, n_valid=None) -> LaneState:
    """Load one problem — or a batch — into lane(s) ``lane`` of the pool.

    ``lane`` is a traced int (K (M, N), a (M,), b (N,)) or a (k,) int
    vector (K (k, M, N), a (k, M), b (k, N)) — a whole scheduling round's
    admissions land in ONE pool update instead of k full-pytree copies.
    K/a/b are zero-padded to the pool shape. The carried column sums are
    initialized from the *stored* (possibly bf16-downcast) matrix, so a
    lane's trajectory is bit-identical to ``solve_fused_batched`` on the
    same problem.

    ``m_valid`` / ``n_valid`` (optional, int or (k,) vectors) record — and
    enforce, by masking the payload to exact zeros beyond them — each
    problem's live extent, which may be strictly smaller than the payload
    shape: the cross-bucket lane-sharing groundwork. A problem admitted
    with valid counts (M', N') into any pool wide enough for them computes
    the bit-identical iterate on its valid region as in a pool of its own
    bucket shape (appended zeros are exact identities of every reduction;
    property-tested in tests/test_cluster.py).
    """
    return _lane_admit(state, lane, K, a, b, m_valid, n_valid)


def _lane_admit(state: LaneState, lane, K, a, b, m_valid,
                n_valid) -> LaneState:
    Mp, Np = state.P.shape[1:]
    Kp, ap, bp, mv, nv = _pad_admit_payload(Mp, Np, K, a, b, m_valid,
                                            n_valid, state.P.dtype)
    return LaneState(
        P=state.P.at[lane].set(Kp),
        colsum=state.colsum.at[lane].set(Kp.astype(jnp.float32).sum(-2)),
        a=state.a.at[lane].set(ap),
        b=state.b.at[lane].set(bp),
        frow=state.frow.at[lane].set(1.0),
        iters=state.iters.at[lane].set(0),
        converged=state.converged.at[lane].set(False),
        active=state.active.at[lane].set(True),
        m_valid=state.m_valid.at[lane].set(mv),
        n_valid=state.n_valid.at[lane].set(nv),
        healthy=state.healthy.at[lane].set(True))


# ---- packed, donated admission (the serving scheduler's pool update) ------
#
# A pool update ships its host payload as ONE float32 and ONE int32 buffer
# and lands it with one launch that writes the donated pool in place —
# three device calls, against one transfer per field plus an eager op per
# step of the mask and the mirror. The layout below is the contract between
# the host staging (``admit_buffers``) and the launch (``lane_admit_packed``).

def _admit_layout(L: int, Mb: int, Nb: int, d: int | None):
    """(float32 field shapes, int32 field shapes) of a packed payload of
    ``L`` problems padded to the bucket (Mb, Nb): dense ``(K, a, b)`` /
    ``(lanes,)`` when ``d`` is None, else point clouds ``(x, xn, y, yn, a,
    b)`` / ``(lanes, m_valid, n_valid)``, with the column cloud at the
    mirror's lane-aligned width so the mirror never pads."""
    if d is None:
        return ((L, Mb, Nb), (L, Mb), (L, Nb)), ((L,),)
    Nc = Nb + (-Nb) % _LANE
    return (((L, Mb, d), (L, Mb), (L, Nc, d), (L, Nc), (L, Mb), (L, Nb)),
            ((L,), (L,), (L,)))


def _unpack(buf, shapes):
    """Split a flat buffer into consecutive fields of ``shapes`` — numpy
    views on the host, slices inside the launch."""
    out, off = [], 0
    for s in shapes:
        n = int(np.prod(s))
        out.append(buf[off:off + n].reshape(s))
        off += n
    return out


def admit_buffers(L: int, Mb: int, Nb: int, d: int | None = None):
    """Zeroed host staging for ``lane_admit_packed``:
    ``(f32, i32, f32_fields, i32_fields)``. The fields are numpy views
    into the two flat buffers, so filling them fills what ships."""
    fs, ints = _admit_layout(L, Mb, Nb, d)
    f32 = np.zeros(sum(int(np.prod(s)) for s in fs), np.float32)
    i32 = np.zeros(sum(int(np.prod(s)) for s in ints), np.int32)
    return f32, i32, _unpack(f32, fs), _unpack(i32, ints)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("bucket", "d", "reg", "scale"))
def lane_admit_packed(state: LaneState, f32: jax.Array, i32: jax.Array, *,
                      bucket: tuple[int, int], d: int | None = None,
                      reg: float = 1.0, scale: float = 1.0) -> LaneState:
    """One pool update from an ``admit_buffers(state.num_lanes, *bucket,
    d)`` payload, in one launch that donates (and so writes in place) the
    pool ``state``.

    Dense (``d`` None): ``lane_admit(state, lanes, K, a, b)``. Point
    clouds: the Gibbs mirror of ``PointCloudGeometry.kernel(reg)`` —
    the same jitted mirror, so the same arithmetic and barrier — masked
    to exact zeros beyond each problem's valid counts, then the same
    lane scatter. Either way the pool ends bit-identical to the eager
    path's, and one pool compiles one signature per payload kind.
    """
    Mb, Nb = bucket
    fs, ints = _admit_layout(state.num_lanes, Mb, Nb, d)
    if d is None:
        K, a, b = _unpack(f32, fs)
        (lanes,) = _unpack(i32, ints)
        return _lane_admit(state, lanes, K, a, b, None, None)
    x, xn, y, yn, a, b = _unpack(f32, fs)
    lanes, mv, nv = _unpack(i32, ints)
    K = _kernel_mirror(x, xn, y, yn, reg=reg, scale=scale)[..., :Nb]
    K = jnp.where(valid_mask(Mb, Nb, mv, nv), K, 0.0)
    return _lane_admit(state, lanes, K, a, b, None, None)


@jax.jit
def lane_evict(state: LaneState, lane) -> LaneState:
    """Free lane(s) ``lane`` (int or (k,) int vector): zero the problem(s)
    and drop the active flag — one pool update however many lanes retire.

    Zero rows/cols are exact no-ops for the rescaling math, so an idle lane
    costs only the (already-paid) bandwidth of its share of the stack.
    """
    return LaneState(
        P=state.P.at[lane].set(jnp.zeros(state.P.shape[1:], state.P.dtype)),
        colsum=state.colsum.at[lane].set(0.0),
        a=state.a.at[lane].set(0.0),
        b=state.b.at[lane].set(0.0),
        frow=state.frow.at[lane].set(1.0),
        iters=state.iters.at[lane].set(0),
        converged=state.converged.at[lane].set(False),
        active=state.active.at[lane].set(False),
        m_valid=state.m_valid.at[lane].set(0),
        n_valid=state.n_valid.at[lane].set(0),
        healthy=state.healthy.at[lane].set(True))


@functools.partial(jax.jit, static_argnames=("max_iters",))
def lane_done(state: LaneState, max_iters: int) -> jax.Array:
    """(L,) bool: lane holds a finished problem — converged, at the cap,
    or frozen unhealthy (a poisoned lane stops advancing the moment its
    flag clears, so "unhealthy" is a terminal disposition too)."""
    return state.active & (state.converged | (state.iters >= max_iters)
                           | ~state.healthy)


def solve_fused_stepped(state: LaneState, n_iters: int, cfg: UOTConfig, *,
                        block_m: int | None = None,
                        interpret: bool | None = None,
                        impl: str | None = None) -> LaneState:
    """Advance every unfinished lane by up to ``n_iters`` iterations.

    The steppable form of ``solve_fused_batched``: one call runs a *chunk*
    of Algorithm-1 iterations on the whole lane pool from explicit carried
    state and returns the new state — solver control flow (convergence
    eviction, admission, deadline scheduling) lives on the host between
    chunks. Per iteration a lane is updated iff it is active, not yet
    converged, and below ``cfg.num_iters``; with ``cfg.tol`` set, a lane
    whose row-factor stationarity drift ``max|frow_t - frow_{t-1}|``
    reaches tol has ``converged`` latched and is frozen at exactly that
    iterate, so a lane's final answer is independent of chunk boundaries
    and of whatever else shares the pool — and equal to the single-problem
    tol solve. ``impl='kernel'`` (Pallas, via the frow-emitting batched
    kernel) and ``impl='jnp'`` stream the pool through HBM every
    iteration; ``impl='resident'`` runs the whole chunk with each lane's
    tile VMEM-resident (``solve_fused_stepped_resident``), and
    ``impl='auto'`` routes by ``resident_fits`` — fp32 pools only, since
    the resident chunk rounds sub-fp32 storage per chunk rather than per
    iteration, which would break chunk-boundary invariance.
    """
    impl = _impl_default(impl, _interpret_default(interpret))
    L, Mp, Np = state.P.shape
    s = jnp.dtype(state.P.dtype).itemsize
    if impl in ("auto", "resident"):
        if _resolve_auto(impl, Mp, Np, cfg, state.P.dtype,
                         stepped_sdt=state.P.dtype):
            return _profiled(
                "chunk", lambda: solve_fused_stepped_resident(
                    state, n_iters, cfg, interpret=interpret),
                M=Mp, N=Np, itemsize=s, impl="resident", lanes=L,
                iters=n_iters)
        impl = None  # over budget (or sub-fp32 pool): streamed default
    return _profiled(
        "chunk", lambda: _solve_fused_stepped_streamed(
            state, n_iters, cfg, block_m=block_m, interpret=interpret,
            impl=impl),
        M=Mp, N=Np, itemsize=s, impl="streamed", lanes=L, iters=n_iters)


def solve_fused_stepped_resident(state: LaneState, n_iters: int,
                                 cfg: UOTConfig, *,
                                 interpret: bool | None = None,
                                 impl: str | None = None) -> LaneState:
    """``solve_fused_stepped`` with the whole chunk VMEM-resident per lane.

    One launch advances every live lane up to ``n_iters`` iterations with
    its tile loaded on-chip once (read + write MN per CHUNK instead of per
    iteration); per-lane gating and the tol freeze run inside the kernel's
    while_loop, so iterates, iteration counts, and chunk-boundary behavior
    match the streamed stepped path exactly for fp32 pools. ``impl``
    selects the flavor within the tier: 'kernel' is
    ``uot_resident.resident_stepped`` (TPU default; interpretable), 'jnp'
    (non-TPU default) reuses the streamed XLA chunk — already one
    executable per chunk — with the pool upcast once at chunk entry and
    downcast once at exit (a no-op for fp32 pools, the per-chunk-rounding
    semantics of the resident kernel for sub-fp32 ones).
    """
    interpret = _interpret_default(interpret)
    if impl not in (None, "kernel", "jnp"):
        raise ValueError(f"resident flavor must be None, 'kernel' or 'jnp', "
                         f"got {impl!r}")
    Mp, Np = state.P.shape[1:]
    if not resident_fits(Mp, Np, cfg, storage_dtype=state.P.dtype):
        raise ValueError(
            f"({Mp}, {Np}) lane pool exceeds the resident VMEM budget; use "
            f"impl='auto' to fall back to the streamed tier")
    flavor = _impl_default(impl, interpret)
    if flavor == "jnp":
        sdt = state.P.dtype
        st = dataclasses.replace(state, P=state.P.astype(jnp.float32))
        st = _solve_fused_stepped_streamed(st, n_iters, cfg,
                                           interpret=interpret, impl="jnp")
        return dataclasses.replace(st, P=st.P.astype(sdt))
    # The resident kernel predates the health flag and is kept unchanged:
    # unhealthy lanes are gated out by feeding them in as converged (the
    # kernel's freeze semantics are exactly the containment we want), and
    # fresh poison is detected at CHUNK granularity from the returned
    # frow/colsum — still O(L*(M+N)), still no M*N rescan. A lane that
    # goes non-finite mid-chunk burns the rest of its own chunk budget
    # before freezing (per-lane while_loops are independent, so no other
    # lane pays anything); the streamed path detects per iteration.
    P, colsum, frow, iters, conv = uot_resident.resident_stepped(
        state.P, state.colsum, state.frow, state.iters,
        state.converged | ~state.healthy,
        state.active, state.a, state.b, fi=cfg.fi, n_iters=n_iters,
        num_iters=cfg.num_iters, tol=cfg.tol, interpret=interpret)
    ran = (state.active & state.healthy & ~state.converged
           & (state.iters < cfg.num_iters))
    finite = (jnp.isfinite(frow).all(axis=-1)
              & jnp.isfinite(colsum).all(axis=-1))
    healthy = state.healthy & (finite | ~ran)
    converged = jnp.where(state.healthy, conv > 0, state.converged)
    return LaneState(P=P, colsum=colsum, a=state.a, b=state.b, frow=frow,
                     iters=iters, converged=converged & healthy,
                     active=state.active,
                     m_valid=state.m_valid, n_valid=state.n_valid,
                     healthy=healthy)


@functools.partial(jax.jit, static_argnames=("n_iters", "cfg", "block_m",
                                             "interpret", "impl"))
def _solve_fused_stepped_streamed(state: LaneState, n_iters: int,
                                  cfg: UOTConfig, *,
                                  block_m: int | None = None,
                                  interpret: bool | None = None,
                                  impl: str | None = None) -> LaneState:
    interpret = _interpret_default(interpret)
    impl = _impl_default(impl, interpret)
    Mp, Np = state.P.shape[1:]
    sdt = state.P.dtype
    bm = block_m or pick_block_m(Mp, Np, sdt.itemsize)
    while Mp % bm:
        bm //= 2
    fi = cfg.fi

    def body(_, st):
        upd = (st.active & ~st.converged & st.healthy
               & (st.iters < cfg.num_iters))
        P, colsum, frow = _stepped_iter(
            st.P, st.colsum, upd, ap=st.a, bp=st.b, fi=fi, sdt=sdt,
            impl=impl, bm=bm, interpret=interpret)
        # Traffic-free lane-health detector: any NaN/Inf a lane produces
        # must pass through its row factors or carried column sums (the
        # safe divisions map a poisoned tile to poisoned factors before
        # they can silently renormalize it), and both are O(L*(M+N))
        # values this check already holds — the M*N tile is never
        # rescanned. The flag latches False and drops the lane out of
        # ``upd``, freezing it exactly like a converged lane: per-lane
        # math is independent, so every other lane's iterate stays
        # bit-identical to a fault-free pool (asserted in
        # tests/test_faults.py). NB a frozen lane's raw frow may itself
        # be non-finite garbage — gating on ``upd`` keeps stale poison
        # from re-clearing anything.
        finite = (jnp.isfinite(frow).all(axis=-1)
                  & jnp.isfinite(colsum).all(axis=-1))
        healthy = st.healthy & (finite | ~upd)
        conv = st.converged
        if cfg.tol is not None:
            drift = lane_factor_drift(frow, st.frow)
            conv = conv | (upd & healthy & (drift <= cfg.tol))
        frow = jnp.where((upd & healthy)[:, None], frow, st.frow)
        return LaneState(P=P, colsum=colsum, a=st.a, b=st.b, frow=frow,
                         iters=st.iters + upd.astype(jnp.int32),
                         converged=conv, active=st.active,
                         m_valid=st.m_valid, n_valid=st.n_valid,
                         healthy=healthy)

    return jax.lax.fori_loop(0, n_iters, body, state)


@functools.partial(jax.jit, static_argnames=("cfg", "block_m", "block_n",
                                             "interpret", "storage_dtype"))
def solve_halfpass(A0: jax.Array, a: jax.Array, b: jax.Array, cfg: UOTConfig,
                   *, block_m: int = 256, block_n: int = 512,
                   interpret: bool | None = None, storage_dtype=None):
    """Wide-N fallback: iteration = two half-fused passes (paper GPU design).

    Supports the same bf16-storage / fp32-accumulation mode as solve_fused.
    """
    interpret = _interpret_default(interpret)
    M, N = A0.shape
    sdt = _storage(cfg, storage_dtype)
    Ap = pad_to(A0.astype(sdt), block_m, block_n)
    ap = pad_vec(a, block_m)
    bp = pad_vec(b, block_n)
    fi = cfg.fi

    # initial column sums via a rows-scale pass with unit factors
    _, colsum = uot_halfpass.scale_rows_accum_cols(
        Ap, jnp.ones((Ap.shape[0],), jnp.float32),
        block_m=block_m, block_n=block_n, interpret=interpret)

    def body(_, carry):
        A, colsum = carry
        fcol = rescale_factors(bp, colsum, fi)
        A, rowsum = uot_halfpass.scale_cols_accum_rows(
            A, fcol, block_m=block_m, block_n=block_n, interpret=interpret)
        frow = rescale_factors(ap, rowsum, fi)
        A, colsum = uot_halfpass.scale_rows_accum_cols(
            A, frow, block_m=block_m, block_n=block_n, interpret=interpret)
        return A, colsum

    Ap, colsum = jax.lax.fori_loop(0, cfg.num_iters, body, (Ap, colsum))
    return Ap[:M, :N], colsum[:N]


@functools.partial(jax.jit, static_argnames=("cfg", "block_m", "interpret",
                                             "materialize"))
def solve_uv(K: jax.Array, a: jax.Array, b: jax.Array, cfg: UOTConfig, *,
             block_m: int | None = None, interpret: bool | None = None,
             materialize: bool = True):
    """Beyond-paper read-only-pass solver (POT u/v semantics).

    K may be bf16 (accumulation fp32). Returns (P or None, (u, v)).
    """
    interpret = _interpret_default(interpret)
    M, N = K.shape
    bm = block_m or pick_block_m(M, N, jnp.dtype(K.dtype).itemsize)
    Kp = pad_to(K, bm, _LANE)
    ap = pad_vec(a, bm)
    bp = pad_vec(b, _LANE)
    fi = cfg.fi

    v0 = jnp.ones((Kp.shape[1],), jnp.float32)

    def body(_, v):
        u, ktu = uot_uv_fused.uv_iteration(
            Kp, v, ap, fi=fi, block_m=bm, interpret=interpret)
        return rescale_factors(bp, ktu, fi)

    v = jax.lax.fori_loop(0, cfg.num_iters, body, v0)
    # one extra half-iteration to get the final u consistent with v
    u, _ = uot_uv_fused.uv_iteration(
        Kp, v, ap, fi=fi, block_m=bm, interpret=interpret)

    if materialize:
        P = uot_uv_fused.materialize_coupling(
            Kp, u, v, block_m=bm, interpret=interpret)[:M, :N]
    else:
        P = None
    return P, (u[:M], v[:N])


@functools.partial(jax.jit, static_argnames=("cfg", "block_m", "interpret",
                                             "materialize", "impl"))
def solve_uv_batched(K: jax.Array, a: jax.Array, b: jax.Array,
                     cfg: UOTConfig, *, block_m: int | None = None,
                     interpret: bool | None = None, materialize: bool = True,
                     impl: str | None = None):
    """Batched read-only-pass u/v solver: K (B, M, N), a (B, M), b (B, N).

    K may be bf16 (accumulation fp32). ``impl`` is 'kernel' or 'jnp' as in
    solve_fused_batched (no resident tier: the u/v pass is read-only, so
    its streamed form already moves only M*N read bytes per iteration).
    Returns (P or None, (u, v)) with P (B, M, N) fp32, u (B, M), v (B, N).
    """
    interpret = _interpret_default(interpret)
    impl = _impl_default(impl, interpret)
    if impl not in ("kernel", "jnp"):
        raise ValueError(f"solve_uv_batched has no resident tier; impl must "
                         f"be 'kernel' or 'jnp', got {impl!r}")
    B, M, N = K.shape
    bm = block_m or pick_block_m(M, N, jnp.dtype(K.dtype).itemsize)
    Kp = pad_to(K, bm, _LANE)
    ap = pad_vec(a, bm)
    bp = pad_vec(b, _LANE)
    fi = cfg.fi

    v0 = jnp.ones((B, Kp.shape[2]), jnp.float32)

    if impl == "jnp":
        def uv_iter(v):
            Kv = jnp.einsum("bmn,bn->bm", Kp.astype(jnp.float32), v)
            u = rescale_factors(ap, Kv, fi)
            ktu = jnp.einsum("bmn,bm->bn", Kp.astype(jnp.float32), u)
            return u, ktu
    else:
        def uv_iter(v):
            return uot_batched.batched_uv_iteration(
                Kp, v, ap, fi=fi, block_m=bm, interpret=interpret)

    def body(_, v):
        _, ktu = uv_iter(v)
        return rescale_factors(bp, ktu, fi)

    v = jax.lax.fori_loop(0, cfg.num_iters, body, v0)
    u, _ = uv_iter(v)

    if not materialize:
        return None, (u[:, :M], v[:, :N])
    if impl == "jnp":
        P = (u[:, :, None] * Kp.astype(jnp.float32)
             * v[:, None, :])[:, :M, :N]
    else:
        P = uot_batched.batched_materialize_coupling(
            Kp, u, v, block_m=bm, interpret=interpret)[:, :M, :N]
    return P, (u[:, :M], v[:, :N])


# ---- shape-bucketed ragged batching ---------------------------------------

def bucket_shape(M: int, N: int, m_bucket: int = 64,
                 n_bucket: int = _LANE) -> tuple[int, int]:
    """The padded (M, N) bucket a problem of shape (M, N) lands in."""
    return (M + (-M) % m_bucket, N + (-N) % n_bucket)


def bucket_problems(shapes, m_bucket: int = 64, n_bucket: int = _LANE):
    """Group problem indices by padded-shape bucket.

    ``shapes`` is a sequence of (M, N). Returns ``{(Mb, Nb): [indices]}``
    with insertion order preserved within each bucket.
    """
    buckets: dict[tuple[int, int], list[int]] = {}
    for idx, (M, N) in enumerate(shapes):
        buckets.setdefault(bucket_shape(M, N, m_bucket, n_bucket),
                           []).append(idx)
    return buckets


# The bucketed path canonicalizes each chunk's batch to a power of two so
# repeated flushes with jittered queue depths land on the same jit cache
# entry instead of recompiling per flush. The counters exist so the cache
# behavior is *assertable* (tests) and observable (engine telemetry);
# jax.jit itself holds the compiled executables.
_BUCKETED_STATS = {"hits": 0, "misses": 0}
_BUCKETED_KEYS: set = set()


def bucketed_cache_stats() -> dict:
    """{'hits': ..., 'misses': ...} of bucketed-solve specializations.

    A *miss* is a (padded shape, canonical batch, dtypes, impl, interpret,
    cfg) combination seen for the first time in this process (it triggers a
    jit trace/compile); a *hit* reuses an existing compiled bucket solve.
    """
    return dict(_BUCKETED_STATS)


def reset_bucketed_cache_stats() -> None:
    """Zero the hit/miss counters and forget seen keys (for tests)."""
    _BUCKETED_STATS.update(hits=0, misses=0)
    _BUCKETED_KEYS.clear()


def canonical_batch(n: int, max_batch: int) -> int:
    """Round a chunk's batch up to the next power of two, capped at
    ``max_batch``. Pad slots are all-zero problems — exact no-ops — and the
    rounding collapses the jit-key space from one entry per queue depth to
    O(log max_batch) entries per bucket shape."""
    B = 1
    while B < n:
        B *= 2
    return min(B, max_batch)


def solve_fused_bucketed(problems, cfg: UOTConfig, *,
                         interpret: bool | None = None, storage_dtype=None,
                         impl: str | None = None, max_batch: int = 64,
                         m_bucket: int = 64, n_bucket: int = _LANE):
    """Solve a ragged list of problems via shape-bucketed batched launches.

    ``problems`` is a sequence of (A0, a, b) triples with per-problem shapes.
    Problems are grouped into padded-shape buckets; each bucket is zero-padded
    to its (Mb, Nb), stacked, and solved by ``solve_fused_batched`` in chunks
    of at most ``max_batch``. Zero padding is exact (padded rows/cols carry
    zero mass and unit factors), so each answer equals its standalone solve.

    ``impl='auto'`` is resolved per bucket chunk by ``solve_fused_batched``
    (the tier choice depends only on the bucket's padded shape and dtypes,
    so it is deterministic per cache key). Each chunk's batch dimension is
    rounded up to ``canonical_batch`` with
    zero problems, so flushes whose bucket shapes repeat reuse the compiled
    solve (see ``bucketed_cache_stats``). The padded stack is assembled
    host-side in numpy: device-side pad/stack would trace per batch
    *composition* (arity x per-problem shapes), an unbounded jit-key space
    that recompiles on nearly every flush under ragged traffic.

    Returns a list of (P, colsum) aligned with the input order.
    """
    interpret = _interpret_default(interpret)
    impl = _impl_default(impl, interpret)
    sdt = _storage(cfg, storage_dtype)
    shapes = [tuple(p[0].shape) for p in problems]
    results: list = [None] * len(problems)
    for (Mb, Nb), idxs in bucket_problems(shapes, m_bucket, n_bucket).items():
        for lo in range(0, len(idxs), max_batch):
            chunk = idxs[lo:lo + max_batch]
            Bpad = canonical_batch(len(chunk), max_batch)
            A0 = np.asarray(problems[chunk[0]][0])
            A = np.zeros((Bpad, Mb, Nb), A0.dtype)
            a = np.zeros((Bpad, Mb), np.asarray(problems[chunk[0]][1]).dtype)
            b = np.zeros((Bpad, Nb), np.asarray(problems[chunk[0]][2]).dtype)
            for k, i in enumerate(chunk):
                M, N = shapes[i]
                A[k, :M, :N] = np.asarray(problems[i][0])
                a[k, :M] = np.asarray(problems[i][1])
                b[k, :N] = np.asarray(problems[i][2])
            A, a, b = jnp.asarray(A), jnp.asarray(a), jnp.asarray(b)
            # mirror the real jit cache key: avals (shapes + all three
            # dtypes) and the static args as passed (raw storage_dtype,
            # not just the resolved sdt)
            key = (A.shape, str(A.dtype), str(a.dtype), str(b.dtype),
                   str(sdt), str(storage_dtype), impl, interpret, cfg)
            if key in _BUCKETED_KEYS:
                _BUCKETED_STATS["hits"] += 1
            else:
                _BUCKETED_KEYS.add(key)
                _BUCKETED_STATS["misses"] += 1
            P, colsum = solve_fused_batched(
                A, a, b, cfg, interpret=interpret,
                storage_dtype=storage_dtype, impl=impl)
            # one host transfer per chunk, then numpy copies per problem —
            # device-side P[k, :M, :N] would compile a slice per (position,
            # problem shape) signature, unbounded under ragged traffic, and
            # returning views would pin the whole padded chunk for as long
            # as any one result is retained
            P, colsum = np.asarray(P), np.asarray(colsum)
            for k, i in enumerate(chunk):
                M, N = shapes[i]
                results[i] = (P[k, :M, :N].copy(), colsum[k, :N].copy())
    return results
