"""Continuous-batching UOT scheduler: solver lanes as serving slots.

The third serving tier (see ``repro.serve``'s module docstring for the
ladder). ``UOTBatchEngine.flush()`` is a barrier: every request in a flush
waits for the slowest problem of its bucket, and requests that arrive while
a flush is running wait for the whole thing. This module replaces the
barrier with the LLM continuous-batching shape, applied to solver state
instead of KV caches:

* one fixed **lane pool** per (m_bucket, n_bucket) padded-shape bucket — a
  ``kernels.ops.LaneState`` stack advanced a *chunk* of Algorithm-1
  iterations at a time by ``ops.solve_fused_stepped`` (one batched launch
  per chunk, Pallas ``'kernel'`` or vectorized ``'jnp'``);
* between chunks, lanes whose per-lane row-factor stationarity drift passed
  ``cfg.tol`` (or that hit ``cfg.num_iters``) are **evicted** and their
  couplings returned immediately — a fast-converging problem never waits
  for a slow lane-mate;
* queued requests are **admitted** into free or freshly-evicted lanes
  earliest-deadline-first (ties: higher priority, then FIFO), so a late
  urgent request starts solving one chunk-boundary after it arrives instead
  of one full flush later;
* ``submit`` applies **backpressure**: beyond ``max_queue`` waiting
  requests it raises ``QueueFullError`` instead of growing an unbounded
  queue.

Because per-lane math is independent of pool occupancy (free lanes are
zero problems — exact no-ops), every request's answer equals its standalone
solve regardless of arrival order, admission interleaving, or evictions;
tests/test_scheduler.py asserts this property for both impls.

Telemetry: every completed request carries a ``RequestTelemetry`` (wait
time, solve iterations, lane, converged-vs-cap, deadline + whether it was
missed, shed disposition, terminal ``status`` + retry count),
``occupancy_log`` snapshots lane utilization and the running
deadline-miss total per step, and ``stats()`` reports
``deadline_misses`` / ``miss_rate`` / ``shed_dropped`` / ``shed_degraded``
— the inputs for the latency/occupancy/miss numbers in
``benchmarks/bench_serve.py``.

Fault containment (the robustness contract; see ``repro.serve``'s
"Failure model" section for the tier-by-tier story):

* **admission** — ``submit``/``submit_points`` run
  ``core.health.validate_problem`` (``validate=True``): non-finite /
  negative / empty marginals, shape/dtype mismatches, and
  overflow-regime ``(cfg, a, b)`` combinations (the ``uv_safe``
  amplification bound) raise a typed ``InvalidProblemError`` carrying
  the assigned rid — the request is refused with telemetry
  (``status='rejected'``) instead of poisoning a shared lane.
* **in flight** — the stepped advance's lane-health detector
  (``ops.LaneState.healthy``) freezes a lane whose factors/colsums go
  non-finite; eviction sees the flag (and double-checks the evicted
  coupling slice host-side, which also catches poison landing after the
  convergence latch) and quarantines the request. Every OTHER lane is
  bit-identical to a fault-free pool — per-lane math is independent.
* **escalation** — a quarantined request is retried ONCE on
  ``sinkhorn_uot_log`` via ``core.health.escalate_log_solve`` (the
  numerically robust tier, escalated iteration budget). A finite
  escalated coupling completes the request with ``status='retried_ok'``;
  anything else is a typed ``RequestFailure`` (``status='failed'``).
* **resolution** — ``poll`` resolves EVERY submitted rid exactly once:
  the coupling, or a ``RequestFailure``
  (failed / rejected / lost-to-the-result-bound), or None only while
  genuinely pending. A convergence-wanting request that hit
  ``cfg.num_iters`` still returns its capped coupling but is recorded
  ``status='timed_out'``.
* **chaos hook** — ``fault_injector=`` (see ``repro.serve.faults``)
  mutates payloads at submit and may corrupt lane state between steps;
  it exists so the containment above is *tested* under seeded fault
  schedules, not assumed.

Deadline-aware shedding (``shed_policy``): a request whose deadline has
already passed when it reaches admission cannot meet it no matter what —
``'drop'`` refuses it a lane entirely (telemetry-only completion,
``lane=-1``), ``'degrade'`` admits it with a reduced iteration budget
(``degrade_iters``, default one chunk) so it returns a coarse answer
after a single scheduling quantum. ``'none'`` (default) keeps the
serve-everything behavior.

Point-cloud requests (``submit_points``) carry coordinates + precomputed
squared norms — ``(M + N) * (d + 1)`` floats instead of ``M * N`` — and
materialize their Gibbs kernel on-device at admission via the geometry
mirror, so a coordinate request's lane trajectory is bit-identical to
dense submission of ``geometry.kernel(cfg.reg)`` (tests assert it).

With ``impl='auto'`` each pool's chunk advance is routed per bucket shape
by ``ops.resident_fits``: fp32 pools that fit the VMEM budget run their
whole chunk with each lane's tile resident
(``ops.solve_fused_stepped_resident`` — one launch, no per-iteration HBM
round trips), larger or sub-fp32 pools keep the streamed masked kernel.

This scheduler is single-device; ``repro.cluster.ClusterScheduler`` (the
fourth tier) stacks one such lane-pool set per mesh device, advances them
all in one ``shard_map`` launch, and routes over-sized problems to the
distributed gang — with results bit-identical to this class per request.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable

import jax.numpy as jnp
import numpy as np

from repro import obs as obslib
from repro.core.problem import UOTConfig
from repro.core.health import (InvalidProblemError, escalate_log_solve,
                               validate_problem)
from repro.core.predict import (IterPredictor, estimate_truncation_error,
                                measured_seconds_per_iter)
from repro.geometry import PointCloudGeometry
from repro.geometry.sliced import lift_coupling_np, sliced_uot
from repro.kernels import ops
from repro.serve.overload import (BrownoutController, InfeasibleDeadline,
                                  queue_pressure)

# registry counter names shared by both schedulers ("serve.<name>" /
# "cluster.<name>"): the running totals stats() reports — refactored
# from ad-hoc int fields onto repro.obs.MetricsRegistry (PR 7); the
# stats() dict shapes are unchanged
_COUNTER_NAMES = (
    "submitted", "completed", "rejected", "failed", "retried_ok",
    "timed_out", "unhealthy_evictions", "lost_results", "deadline_misses",
    "deadlined_completed", "shed_dropped", "shed_degraded",
    "window_dropped_requests", "window_dropped_occupancy",
    "window_dropped_dispositions")


class QueueFullError(RuntimeError):
    """Raised by submit() when the waiting queue is at max_queue.

    Carries the observed ``queue_depth`` and, when the scheduler's
    service-time model has calibrated (``predictive=True`` and at least
    one completion observed), a ``retry_after`` hint in seconds — the
    predicted time for the backlog to drain one full lane round. Both
    are None-safe: prediction off means ``retry_after is None`` and
    clients fall back to their own backoff base (``submit_with_retry``
    does exactly that).
    """

    def __init__(self, message: str, *, queue_depth: int | None = None,
                 retry_after: float | None = None):
        super().__init__(message)
        self.queue_depth = queue_depth
        self.retry_after = retry_after


def submit_with_retry(scheduler, *args, attempts: int = 6,
                      base_delay: float = 0.05, max_delay: float = 2.0,
                      seed: int = 0, sleep: Callable[[float], None] = None,
                      submit: Callable | None = None, **kwargs) -> int:
    """Client-side backpressure helper: ``scheduler.submit(*args,
    **kwargs)`` with capped exponential backoff on ``QueueFullError``.

    The docstring advice "the caller sheds load or retries later" made
    concrete: up to ``attempts`` tries, sleeping
    ``min(max_delay, base_delay * 2**i) * (0.5 + 0.5 * jitter)`` between
    them — deterministic jitter from ``seed`` (``numpy`` Philox, no global
    RNG state), so a fleet of callers configured with distinct seeds
    decorrelates its retry storms *reproducibly*. After the last failed
    attempt the final ``QueueFullError`` propagates (give-up semantics:
    the caller learns the queue never drained; nothing is silently
    dropped). ``submit=`` overrides the bound method (e.g.
    ``scheduler.submit_points``); ``sleep=`` is injectable for tests and
    simulated clocks — when omitted it resolves to the *scheduler's* own
    injected ``sleep`` (both schedulers accept ``sleep=`` next to
    ``clock=``), so a fake-clock scheduler never races wall time through
    this helper. Validation errors (``InvalidProblemError``) are NOT
    retried — a refused problem stays refused.

    When the raised ``QueueFullError`` carries a ``retry_after`` hint
    (the scheduler's predicted backlog drain time — see
    ``predictive=``), that hint replaces ``base_delay`` as the backoff
    base: the client waits roughly as long as the queue actually needs,
    instead of a blind constant. With prediction off the behavior is
    exactly the historical capped-exponential one.
    """
    if sleep is None:
        sleep = getattr(scheduler, "sleep", None) or time.sleep
    fn = submit if submit is not None else scheduler.submit
    rng = np.random.default_rng(seed)
    for attempt in range(attempts):
        try:
            return fn(*args, **kwargs)
        except QueueFullError as err:
            if attempt == attempts - 1:
                raise
            base = (err.retry_after
                    if getattr(err, "retry_after", None) else base_delay)
            delay = min(max_delay, base * (2.0 ** attempt))
            sleep(delay * (0.5 + 0.5 * float(rng.random())))
    raise AssertionError("unreachable")  # pragma: no cover


@dataclasses.dataclass
class RequestFailure:
    """The typed terminal disposition ``poll`` returns when a request did
    not end in a usable coupling: ``status`` is ``'failed'`` (poisoned in
    flight, escalation also failed), ``'rejected'`` (refused at admission
    or shed-dropped), or ``'lost'`` (completed fine, but the bounded
    result store evicted the coupling before it was polled — the answer
    is gone, the *disposition* is not). ``reason`` is human-readable;
    ``retries`` counts escalation attempts spent."""

    rid: int
    status: str
    reason: str
    retries: int = 0


@dataclasses.dataclass
class ScheduledRequest:
    """A queued UOT problem plus its scheduling attributes.

    Payload stays host-side numpy while queued; the single host->device
    transfer happens at admission (already padded to the bucket shape).
    Point-cloud requests (``submit_points``) carry coordinates + squared
    norms instead of ``K`` — ``(M + N) * (d + 1)`` floats instead of
    ``M * N`` — and materialize their Gibbs kernel on-device at admission.
    """

    rid: int
    K: np.ndarray | None        # (M, N) initial coupling / Gibbs kernel
    a: np.ndarray               # (M,) row marginal
    b: np.ndarray               # (N,) column marginal
    shape: tuple[int, int]
    bucket: tuple[int, int]
    arrival: float
    deadline: float | None = None   # absolute time; None = no deadline
    priority: int = 0               # higher = more urgent (EDF tie-break)
    # coordinate payload (set iff K is None): the geometry-sourced request
    x: np.ndarray | None = None     # (M, d)
    y: np.ndarray | None = None     # (N, d)
    xn: np.ndarray | None = None    # (M,) precomputed squared norms
    yn: np.ndarray | None = None    # (N,)
    scale: float = 1.0
    # deadline-aware shedding state (set at admission time)
    max_iters: int | None = None    # reduced budget for degraded requests
    shed: str | None = None         # None | 'degraded' ('dropped' never
    #                                 occupies a lane, only telemetry)
    # overload-model state (predictive=True; see repro.serve's overload
    # model): ladder level 0/1/2, the admission-time iteration
    # prediction, and the error label attached to degraded answers
    degrade_level: int = 0
    predicted_iters: float | None = None
    est_error: float | None = None
    # fault-containment state
    retries: int = 0                # escalation/requeue attempts spent
    fault: str | None = None        # injector tag (chaos bookkeeping only;
    #                                 the runtime never reads it)

    def edf_key(self):
        """Earliest-deadline-first with priority then FIFO tie-breaks."""
        d = self.deadline if self.deadline is not None else float("inf")
        return (d, -self.priority, self.rid)

    def slack_key(self, service: float | None):
        """Least-slack ordering: EDF on the *latest feasible start time*
        (deadline minus predicted service). Falls back to plain EDF when
        no service prediction is available."""
        if self.deadline is None:
            return (float("inf"), -self.priority, self.rid)
        d = self.deadline - (service or 0.0)
        return (d, -self.priority, self.rid)


@dataclasses.dataclass
class RequestTelemetry:
    """Per-request serving record, filled at eviction."""

    rid: int
    bucket: tuple[int, int]
    lane: int                   # -1 for requests dropped at admission
    arrival: float
    admitted: float
    completed: float
    iters: int
    converged: bool             # False = hit the num_iters cap
    deadline: float | None = None   # the request's absolute deadline
    shed: str | None = None     # 'dropped' / 'degraded' / None
    # terminal disposition: 'ok' | 'retried_ok' (completed on the
    # log-domain escalation tier) | 'timed_out' (capped, coupling still
    # delivered) | 'failed' (typed failure) | 'rejected' (refused at
    # admission / shed-dropped)
    status: str = "ok"
    retries: int = 0            # escalation attempts spent
    # overload-model labels: ladder level (0 = full solve), the error
    # estimate attached to degraded answers (truncation model at level
    # 1, certified sliced gap + MC std err at level 2), and what the
    # admission-time predictor said (None with prediction off)
    degrade_level: int = 0
    est_error: float | None = None
    predicted_iters: float | None = None

    @property
    def wait(self) -> float:
        return self.admitted - self.arrival

    @property
    def latency(self) -> float:
        return self.completed - self.arrival

    @property
    def missed(self) -> bool:
        """Completed after its deadline (False when no deadline was set)."""
        return self.deadline is not None and self.completed > self.deadline


class _LanePool:
    """One shape bucket's lane pool + host-side lane bookkeeping."""

    def __init__(self, bucket: tuple[int, int], num_lanes: int,
                 cfg: UOTConfig, *, storage_dtype=None):
        self.bucket = bucket
        self.cfg = cfg
        self.state = ops.make_lane_state(
            num_lanes, bucket[0], bucket[1], cfg,
            storage_dtype=storage_dtype)
        self.requests: dict[int, ScheduledRequest] = {}   # lane -> request
        self.admitted_at: dict[int, float] = {}           # lane -> time
        self.idle_steps = 0      # consecutive scheduler rounds with 0 lanes

    @property
    def num_lanes(self) -> int:
        return self.state.num_lanes

    def free_lanes(self) -> list[int]:
        return [i for i in range(self.num_lanes) if i not in self.requests]

    @property
    def occupancy(self) -> float:
        return len(self.requests) / self.num_lanes


class UOTScheduler:
    """Deadline-aware continuous batching over steppable UOT lane pools.

    Usage::

        sched = UOTScheduler(UOTConfig(num_iters=100, tol=1e-4))
        rid = sched.submit(K, a, b, deadline=now + 0.5, priority=1)
        results = sched.run()          # {rid: coupling}, or step() manually

    ``chunk_iters`` is the scheduling quantum: smaller chunks admit and
    evict sooner (better tail latency) at the cost of more host round
    trips per solve. ``cfg.tol`` enables convergence eviction; with
    ``tol=None`` every lane runs exactly ``cfg.num_iters`` and the answer
    equals the fixed-iteration ``solve_fused`` exactly.

    Memory is bounded for long-running serving: results not collected from
    a ``step()``/``run()`` return value are held for ``poll`` — which hands
    a result out exactly once (take semantics) — but only the most recent
    ``max_results`` of them (couplings are large; the step/run return is
    the primary delivery); telemetry keeps the most recent ``max_log``
    request records / occupancy snapshots; and a lane pool whose bucket
    has been empty for ``pool_idle_ttl`` consecutive steps is released
    (recreated on demand), so one-off request shapes don't pin device
    memory forever.
    """

    def __init__(self, cfg: UOTConfig, *, lanes_per_pool: int = 8,
                 chunk_iters: int = 4, max_queue: int = 1024,
                 m_bucket: int = 64, n_bucket: int = 128,
                 storage_dtype=None, interpret: bool | None = None,
                 impl: str | None = None, max_log: int = 10_000,
                 max_results: int = 256, pool_idle_ttl: int | None = 100,
                 shed_policy: str = "none",
                 degrade_iters: int | None = None,
                 validate: bool = True, retry_escalate: bool = True,
                 escalate_factor: int = 2, fault_injector=None,
                 predictive: bool = False,
                 seconds_per_iter: float | None = None,
                 measurements=None,
                 feasibility_margin: float = 1.0,
                 brownout: "BrownoutController | None" = None,
                 predictor: "IterPredictor | None" = None,
                 sliced_n_proj: int = 32, sliced_seed: int = 0,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep,
                 obs: "obslib.Observability | bool | None" = None,
                 slos=None, op_interval: int = 4):
        if lanes_per_pool < 1:
            raise ValueError("lanes_per_pool must be >= 1")
        if chunk_iters < 1:
            raise ValueError("chunk_iters must be >= 1")
        if shed_policy not in ("none", "drop", "degrade"):
            raise ValueError(f"shed_policy must be 'none', 'drop' or "
                             f"'degrade', got {shed_policy!r}")
        self.cfg = cfg
        self.lanes_per_pool = lanes_per_pool
        self.chunk_iters = chunk_iters
        self.max_queue = max_queue
        self.m_bucket = m_bucket
        self.n_bucket = n_bucket
        self.storage_dtype = storage_dtype
        self.interpret = interpret
        self.impl = impl
        self.max_log = max_log
        self.max_results = max_results
        self.pool_idle_ttl = pool_idle_ttl
        # Deadline-aware shedding: a request whose deadline has ALREADY
        # passed when it reaches the head of the admission queue cannot
        # meet it no matter what — 'drop' refuses it the lane entirely
        # (telemetry-only completion), 'degrade' admits it with a reduced
        # iteration budget (``degrade_iters``, default one chunk) so it
        # returns a coarse answer after a single scheduling quantum
        # instead of occupying a lane for a full solve. 'none' keeps the
        # historical serve-everything behavior. The budget is enforced at
        # chunk granularity (lanes advance ``chunk_iters`` at a time).
        self.shed_policy = shed_policy
        self.degrade_iters = (chunk_iters if degrade_iters is None
                              else degrade_iters)
        # Fault containment: ``validate`` gates the typed admission checks
        # (``core.health.validate_problem``); ``retry_escalate`` gates the
        # one-shot log-domain retry of quarantined (unhealthy-evicted)
        # requests, with ``escalate_factor`` scaling the escalated
        # iteration budget; ``fault_injector`` is the chaos hook
        # (``repro.serve.faults``) — None in production.
        self.validate = validate
        self.retry_escalate = retry_escalate
        self.escalate_factor = escalate_factor
        self.fault_injector = fault_injector
        # Overload model (predictive=True; see repro.serve's overload
        # model section). The service-time model is
        # ``predicted_iters * seconds_per_iter``: iterations from
        # ``core.predict`` (analytic contraction rate + per-bucket EWMA
        # fed by eviction telemetry), seconds-per-iteration either
        # pinned (``seconds_per_iter=``, e.g. a measured value under a
        # simulated clock) or learned online from completions (EWMA of
        # latency/iters; the gate stays inert until the first
        # completion calibrates it — never a guess in fake units).
        # ``feasibility_margin`` scales predicted service before the
        # deadline comparison (>1 = conservative admission). The gate
        # only refuses/degrades when a shed_policy is active ('drop'
        # refuses with InfeasibleDeadline, 'degrade' walks the ladder);
        # with shed_policy='none' prediction still powers least-slack
        # EDF + retry_after hints but never refuses work.
        self.predictive = predictive
        self.feasibility_margin = feasibility_margin
        self.predictor = predictor if predictor is not None else IterPredictor()
        self.brownout = brownout
        if predictive and brownout is None and shed_policy == "degrade":
            self.brownout = BrownoutController()
        self.sliced_n_proj = sliced_n_proj
        self.sliced_seed = sliced_seed
        self._spi_pinned = seconds_per_iter
        self._spi_ewma: float | None = None
        self._iters_ewma: float | None = None
        # Measured performance (repro.obs.measure): a MeasurementStore
        # recorded on THIS machine. Two consumers: the service-time model
        # converts predicted iterations to seconds via measured chunk
        # cost (after the pinned value, before the completion EWMA — a
        # pinned value is the caller asserting units, e.g. a simulated
        # clock, and must win), and impl='auto' chunk dispatch consults
        # the store's per-tier cells via ops.dispatch_advisor. NB the
        # store holds wall-clock us: do not combine with a simulated
        # clock unless the trace was measured in the same units.
        self.measurements = measurements
        self._advisor = (obslib.MeasuredDispatch(measurements)
                         if measurements is not None else None)
        self._pending_completed: dict[int, np.ndarray] = {}
        self.clock = clock
        self.sleep = sleep
        # Observability: None -> a fresh enabled bundle on this scheduler's
        # clock, chained to the process-global one; False -> metrics only
        # (stats() needs the registry) with tracing/traffic disabled and
        # no global chaining; or pass a bundle. See repro.obs.
        if obs is None:
            obs = obslib.Observability(clock=clock)
        elif obs is False:
            obs = obslib.Observability(enabled=False, clock=clock,
                                       chain=False)
        self.obs = obs
        # Operational plane (repro.obs "Operational telemetry"): rolling
        # windows over this registry, burn-rate SLO alerting (``slos=``,
        # a list of obslib.SLO — empty by default so nothing pages
        # unless objectives were declared), and the black-box flight
        # recorder, all on THIS scheduler's clock. A firing alert
        # freezes the flight ring (_on_alert). Null twins under
        # obs=False — the per-round hook costs three no-op calls. A
        # bundle that already carries a plane (caller attached their
        # own) is kept unless this scheduler declares objectives.
        if not obs.windows.enabled or slos:
            obs.attach_operational(slos=slos or (), clock=clock,
                                   on_alert=(self._on_alert,))
        self.flight = obs.flight
        self.exporter = obs.exporter
        # window tick + SLO evaluation run every ``op_interval`` rounds
        # (and whenever the scheduler drains): the full-registry
        # snapshot is the plane's only per-round O(metrics) cost, and
        # decimating it keeps the whole plane inside bench_obs's <= 5%
        # bar without losing alerting resolution (burn-rate windows are
        # many rounds wide by construction)
        self.op_interval = max(1, int(op_interval))
        reg = obs.registry
        self._c = {k: reg.counter("serve." + k) for k in _COUNTER_NAMES}
        self._h_wait = reg.histogram("serve.wait_s")
        self._h_latency = reg.histogram("serve.latency_s")
        self._h_iters = reg.histogram("serve.iters",
                                      buckets=obslib.DEFAULT_COUNT_BUCKETS)
        self._g_queued = reg.gauge("serve.queued")
        self._g_in_flight = reg.gauge("serve.in_flight")
        self._g_occupancy = reg.gauge("serve.occupancy")
        self._c_dispatch = {k: reg.counter("serve.dispatch." + k)
                            for k in ("resident", "streamed")}
        # admission's pool updates, and the host-to-device transfers plus
        # launches they cost
        self._c_admit_updates = reg.counter("serve.admit.updates")
        self._c_admit_calls = reg.counter("serve.admit.device_calls")
        # overload-model observability: degrade-ladder activity per
        # level, feasibility refusals, and the iteration predictor's
        # relative absolute error (|predicted - actual| / actual) so the
        # control loop is auditable from the registry alone
        self._c_infeasible = reg.counter("serve.admission.infeasible")
        self._c_degrade = {lvl: reg.counter(f"serve.degrade.l{lvl}")
                           for lvl in (1, 2)}
        self._g_brownout = reg.gauge("serve.degrade.brownout_level")
        self._h_pred_err = reg.histogram("serve.predict.rel_err")

        self._queue: list[ScheduledRequest] = []
        self._pools: dict[tuple[int, int], _LanePool] = {}
        self._next_rid = 0
        self._results: dict[int, np.ndarray] = {}
        # rid -> RequestFailure: the terminal dispositions of requests
        # that did NOT end in a polled coupling. Kept separate from (and
        # much smaller than) the coupling store so the ``max_results``
        # bound can never erase the *fact* of a failure — only couplings
        # are size-bounded, and a coupling evicted un-polled leaves a
        # 'lost' tombstone here. Trimmed FIFO at ``max_log``.
        self._dispositions: dict[int, RequestFailure] = {}
        self._steps = 0
        self.request_log: list[RequestTelemetry] = []
        self.occupancy_log: list[dict] = []
        # The running totals (deadline accounting, shed decisions,
        # fault-containment outcomes) live in ``self._c`` registry
        # counters — exact, survive request_log trimming, and visible in
        # the process-global registry dump. stats() reads them back.

    # ---- submission -------------------------------------------------------

    def _reject(self, rid: int, bucket, deadline, err: InvalidProblemError,
                now: float) -> None:
        """Record a refused admission: telemetry + a typed disposition so
        ``poll(rid)`` resolves the rid instead of returning pending-forever,
        then re-raise with the rid attached."""
        self._c["rejected"].inc()
        self._log_request(RequestTelemetry(
            rid=rid, bucket=bucket, lane=-1, arrival=now, admitted=now,
            completed=now, iters=0, converged=False, deadline=deadline,
            status="rejected"))
        self.obs.tracer.emit(rid, "complete", status="rejected",
                             reason=err.reason)
        self._store_disposition(RequestFailure(
            rid=rid, status="rejected", reason=f"{err.reason}: {err}"))
        raise err

    def _store_disposition(self, failure: RequestFailure) -> None:
        self._dispositions[failure.rid] = failure
        while len(self._dispositions) > self.max_log:
            self._dispositions.pop(next(iter(self._dispositions)))
            self._c["window_dropped_dispositions"].inc()
        fl = self.obs.flight
        if fl.enabled:
            fl.note("failure", rid=failure.rid, status=failure.status)
            if failure.status == "failed":
                # dump_on RequestFailure: an unrecovered fault is an
                # incident — freeze the rounds that led up to it
                fl.dump("request_failure",
                        reason=f"rid {failure.rid}: {failure.reason}")

    def _log_request(self, rec: RequestTelemetry) -> None:
        """THE append path for request telemetry: append, then trim to
        ``max_log`` immediately, counting what fell off. Trimming only at
        the per-step occupancy snapshot (the historical behavior) missed
        every record appended between snapshots — shed-drops and
        submit-time rejections landed untrimmed and, worse, uncounted
        when a later snapshot trimmed them away. One helper, one window,
        one counter."""
        self.request_log.append(rec)
        excess = len(self.request_log) - self.max_log
        if excess > 0:
            self._c["window_dropped_requests"].inc(excess)
            del self.request_log[:excess]

    # ---- service-time model (predictive=True) -----------------------------

    def _seconds_per_iter(self, bucket=None) -> float | None:
        """Pinned value, else the measured chunk rate (per-bucket when
        ``bucket`` is given, else aggregated), else the online EWMA, else
        None (uncalibrated)."""
        if self._spi_pinned is not None:
            return self._spi_pinned
        if self.measurements is not None:
            M, N = bucket if bucket is not None else (None, None)
            spi = measured_seconds_per_iter(self.measurements, M=M, N=N)
            if spi is None and bucket is not None:
                spi = measured_seconds_per_iter(self.measurements)
            if spi is not None:
                return spi
        return self._spi_ewma

    def _predict_request_iters(self, req: ScheduledRequest) -> float:
        return self.predictor.predict(
            self.cfg, bucket=req.bucket,
            mass_a=float(req.a.sum()), mass_b=float(req.b.sum()))

    def _predicted_service(self, req: ScheduledRequest) -> float | None:
        """Predicted lane seconds for ``req``, None while uncalibrated."""
        spi = self._seconds_per_iter(req.bucket)
        if not self.predictive or spi is None:
            return None
        if req.predicted_iters is None:
            req.predicted_iters = self._predict_request_iters(req)
        return req.predicted_iters * spi

    def _retry_after_hint(self) -> float | None:
        """Predicted backlog drain time for QueueFullError: queued work
        (mean observed iterations each) over total lane throughput."""
        spi = self._seconds_per_iter()
        if (not self.predictive or spi is None
                or self._iters_ewma is None):
            return None
        total_lanes = max(
            1, sum(p.num_lanes for p in self._pools.values())
            or self.lanes_per_pool)
        return (len(self._queue) * self._iters_ewma * spi) / total_lanes

    def _feasibility_gate(self, req: ScheduledRequest, now: float,
                          rid: int) -> None:
        """Refuse or degrade a request whose SLO is already unmeetable —
        BEFORE it burns queue slots or lane time. Raises
        ``InfeasibleDeadline`` (shed_policy='drop') or walks the degrade
        ladder (shed_policy='degrade'). No-op when prediction is off,
        uncalibrated, the request has no deadline, or shed_policy='none'
        (prediction then only powers ordering + retry hints)."""
        if (not self.predictive or req.deadline is None
                or self.shed_policy == "none"):
            return
        service = self._predicted_service(req)
        if service is None:
            return
        finish = now + self.feasibility_margin * service
        if finish <= req.deadline:
            return
        if self.shed_policy == "drop":
            self._c_infeasible.inc()
            self.obs.tracer.emit(rid, "shed", policy="infeasible",
                                 predicted_finish=finish,
                                 deadline=req.deadline)
            err = InfeasibleDeadline(
                f"request {rid} cannot meet its deadline: predicted "
                f"finish {finish:.4f} > deadline {req.deadline:.4f} "
                f"(predicted {req.predicted_iters:.0f} iters)",
                rid=rid, deadline=req.deadline, predicted_finish=finish,
                predicted_iters=req.predicted_iters)
            self._reject(rid, req.bucket, req.deadline, err, now)
        # 'degrade': give it the deepest budget that CAN fit, labeled
        self._c_infeasible.inc()
        self._degrade(req, self.max_degrade_level(req))

    def max_degrade_level(self, req: ScheduledRequest) -> int:
        """Level 2 (sliced) needs coordinates to project and a finite
        marginal relaxation (the 1-D FW dual is a KL dual); dense or
        balanced requests top out at the deepest truncation (level 1)."""
        return (2 if req.K is None and np.isfinite(self.cfg.reg_m)
                else 1)

    def _complete_sliced(self, req: ScheduledRequest, now: float) -> None:
        """Finish a level-2 request on the host sliced tier: ``n_proj``
        exact 1-D solves in one vmapped launch (O(n_proj (M+N) log(M+N))
        — no lane, no M*N compute), the per-slice monotone plans averaged
        into the delivered coupling, and the certified error label
        (mean per-slice FW gap + Monte-Carlo std err) on the telemetry.
        Completes THIS scheduling round via the pending buffer."""
        M, N = req.shape
        res = sliced_uot(req.x, req.y, req.a, req.b,
                         rho=float(self.cfg.reg_m), scale=req.scale,
                         n_proj=self.sliced_n_proj, seed=self.sliced_seed)
        P = lift_coupling_np(res, M, N).astype(np.float32)
        req.est_error = res.est_error
        self._pending_completed[req.rid] = self._results[req.rid] = P
        self._trim_results()
        rec = RequestTelemetry(
            rid=req.rid, bucket=req.bucket, lane=-1,
            arrival=req.arrival, admitted=now, completed=now,
            iters=0, converged=True, deadline=req.deadline,
            shed="degraded", status="ok", retries=req.retries,
            degrade_level=2, est_error=res.est_error,
            predicted_iters=req.predicted_iters)
        if rec.deadline is not None:
            self._c["deadlined_completed"].inc()
            self._c["deadline_misses"].inc(int(rec.missed))
        self._c["completed"].inc()
        self._h_wait.observe(rec.wait)
        self._h_latency.observe(rec.latency)
        self._h_iters.observe(0)
        self.obs.tracer.emit(req.rid, "complete", status="ok", iters=0,
                             degrade_level=2, est_error=res.est_error)
        self._log_request(rec)

    def _degrade(self, req: ScheduledRequest, level: int) -> None:
        """Apply degrade-ladder ``level`` to a queued request (idempotent
        upward: a request never degrades *less* than already promised)."""
        level = min(level, self.max_degrade_level(req))
        if level <= req.degrade_level:
            return
        req.degrade_level = level
        if req.shed != "degraded":
            req.shed = "degraded"
            self._c["shed_degraded"].inc()
        self._c_degrade[level].inc()
        self.obs.tracer.emit(req.rid, "degrade", level=level)
        self.obs.flight.note("degrade", rid=req.rid, level=level)
        if level == 1:
            req.max_iters = min(self.cfg.num_iters, self.degrade_iters)
            req.est_error = estimate_truncation_error(
                self.cfg, req.max_iters,
                mass_a=float(req.a.sum()), mass_b=float(req.b.sum()))
        # level 2 (sliced) bypasses the lanes entirely at admission —
        # est_error comes from the solve itself (certified per-slice
        # gap + Monte-Carlo std err), not a model

    def submit(self, K, a, b, *, deadline: float | None = None,
               priority: int = 0) -> int:
        """Enqueue a problem; returns its request id.

        Raises ``QueueFullError`` when ``max_queue`` requests are already
        waiting (in-flight lanes don't count) — the caller sheds load or
        retries later instead of the queue growing without bound (see
        ``submit_with_retry`` for the canonical retry loop). Raises
        ``InvalidProblemError`` (rid attached, telemetry recorded,
        ``poll(rid)`` resolves to the typed failure) for problems the
        admission validator refuses — see the module docstring's fault
        containment notes.
        """
        if len(self._queue) >= self.max_queue:
            raise QueueFullError(
                f"queue at max_queue={self.max_queue}; retry later",
                queue_depth=len(self._queue),
                retry_after=self._retry_after_hint())
        K = np.asarray(K)
        a = np.asarray(a)
        b = np.asarray(b)
        rid = self._next_rid
        self._next_rid += 1
        fault = None
        if self.fault_injector is not None:
            K, a, b, fault = self.fault_injector.on_submit(rid, K, a, b)
            if fault is not None:
                self.obs.flight.note("fault", rid=rid, tag=fault)
        M, N = K.shape
        bucket = ops.bucket_shape(M, N, self.m_bucket, self.n_bucket)
        now = self.clock()
        self._c["submitted"].inc()
        self.obs.tracer.emit(rid, "submit", M=M, N=N, bucket=list(bucket),
                             kind="dense", deadline=deadline,
                             priority=priority)
        if self.validate:
            try:
                validate_problem(self.cfg, a, b, shape=(M, N), rid=rid)
            except InvalidProblemError as err:
                self._reject(rid, bucket, deadline, err, now)
        req = ScheduledRequest(
            rid=rid, K=K, a=a, b=b, shape=(M, N), bucket=bucket,
            arrival=now, deadline=deadline, priority=priority, fault=fault)
        self._feasibility_gate(req, now, rid)   # may raise / degrade
        self._queue.append(req)
        self.obs.tracer.emit(rid, "queue", depth=len(self._queue),
                             route="lane")
        return rid

    def submit_points(self, x, y, a, b, *, scale: float = 1.0,
                      deadline: float | None = None,
                      priority: int = 0) -> int:
        """Enqueue a point-cloud problem: squared-Euclidean cost of the
        (M, d) / (N, d) coordinate clouds, ``C = ||x - y||^2 / scale``.

        The request payload is ``(M + N) * (d + 1)`` floats (coordinates +
        precomputed squared norms) instead of the dense ``M * N`` kernel —
        the Gibbs kernel is materialized on-DEVICE at admission, straight
        into the lane pool. A lane's trajectory is bit-identical to
        ``submit(K=geometry.kernel(cfg.reg), ...)`` for the same
        coordinates (asserted in tests): same mirror arithmetic, same
        pool, same math.
        """
        if len(self._queue) >= self.max_queue:
            raise QueueFullError(
                f"queue at max_queue={self.max_queue}; retry later",
                queue_depth=len(self._queue),
                retry_after=self._retry_after_hint())
        # from_points computes the squared norms ONCE with the shared
        # jitted helper — reusing them at admission is what keeps the
        # batched device materialization bit-identical to a per-request
        # geometry's kernel() (see repro.geometry.pointcloud rule 1)
        with self.obs.phases.phase("serve.points"):
            g = PointCloudGeometry.from_points(x, y, scale=scale)
            coords = dict(x=np.asarray(g.x), y=np.asarray(g.y),
                          xn=np.asarray(g.xn), yn=np.asarray(g.yn))
        M, N = g.shape
        a = np.asarray(a)
        b = np.asarray(b)
        rid = self._next_rid
        self._next_rid += 1
        fault = None
        if self.fault_injector is not None:
            _, a, b, fault = self.fault_injector.on_submit(rid, None, a, b)
            if fault is not None:
                self.obs.flight.note("fault", rid=rid, tag=fault)
        bucket = ops.bucket_shape(M, N, self.m_bucket, self.n_bucket)
        now = self.clock()
        self._c["submitted"].inc()
        self.obs.tracer.emit(rid, "submit", M=M, N=N, bucket=list(bucket),
                             kind="points", deadline=deadline,
                             priority=priority)
        if self.validate:
            try:
                validate_problem(self.cfg, a, b, shape=(M, N), rid=rid)
            except InvalidProblemError as err:
                self._reject(rid, bucket, deadline, err, now)
        req = ScheduledRequest(
            rid=rid, K=None, a=a, b=b, shape=(M, N), bucket=bucket,
            arrival=now, deadline=deadline, priority=priority,
            scale=float(scale), fault=fault, **coords)
        self._feasibility_gate(req, now, rid)   # may raise / degrade
        self._queue.append(req)
        self.obs.tracer.emit(rid, "queue", depth=len(self._queue),
                             route="lane")
        return rid

    @property
    def pending(self) -> int:
        """Requests waiting for a lane."""
        return len(self._queue)

    @property
    def in_flight(self) -> int:
        """Requests currently occupying lanes."""
        return sum(len(p.requests) for p in self._pools.values())

    def poll(self, rid: int):
        """The terminal disposition of ``rid``: the finished coupling, a
        ``RequestFailure`` (failed / rejected / lost), or None only while
        the request is genuinely pending. Nothing vanishes: every
        submitted rid eventually resolves to exactly one non-None value
        (property-tested under fault injection).

        Take semantics: a result is handed out exactly once and then
        dropped, so an uncollected backlog cannot grow without bound.
        """
        with self.obs.phases.phase("serve.poll"):
            out = self._results.pop(rid, None)
            if out is not None:
                self.obs.tracer.emit(rid, "poll", resolved="coupling")
                return out
            out = self._dispositions.pop(rid, None)
            self.obs.tracer.emit(
                rid, "poll",
                resolved="failure" if out is not None else "pending")
            return out

    # ---- the scheduling loop ---------------------------------------------

    def step(self) -> dict[int, np.ndarray]:
        """One scheduling round: evict -> admit -> advance one chunk ->
        upkeep (occupancy snapshot, operational plane).

        Returns the requests completed by this round, ``{rid: P (M, N)}``
        as host numpy arrays (also retained for ``poll``, padding-free
        copies). Eviction happens *before* admission
        so freshly-freed lanes are immediately reusable — the continuous
        part of continuous batching.

        Each phase is a ``PhaseTimer`` phase (``serve.evict`` with a
        ``serve.evict.read`` per evicted lane, ``serve.admit`` with a
        ``serve.admit.launch`` per pool update, ``serve.chunk``,
        ``serve.upkeep``), and so a profiler annotation. Chunk launches
        are not synced or timed here: the next round's flag reads wait
        for them, and a profiler trace gives their device time by op.
        """
        if self.fault_injector is not None:
            self.fault_injector.on_step(self)
        if self.brownout is not None:
            total = (sum(p.num_lanes for p in self._pools.values())
                     or self.lanes_per_pool)
            self._g_brownout.set(self.brownout.observe(
                queue_pressure(len(self._queue), total)))
        ph = self.obs.phases
        with ph.phase("serve.evict"):
            completed = self._evict_finished()
        with ph.phase("serve.admit"):
            self._admit_queued()
        if self._pending_completed:
            # level-2 (sliced) completions produced during admission —
            # delivered with this round's evictions
            completed.update(self._pending_completed)
            self._pending_completed.clear()
        with ph.phase("serve.chunk"):
            for bucket, pool in list(self._pools.items()):
                if pool.requests:
                    pool.idle_steps = 0
                    # the advisor makes impl='auto' routing measurement-
                    # driven when a MeasurementStore was passed
                    with ops.dispatch_counters() as counters, \
                            (ops.dispatch_advisor(self._advisor)
                             if self._advisor is not None
                             else contextlib.nullcontext()):
                        pool.state = ops.solve_fused_stepped(
                            pool.state, self.chunk_iters, self.cfg,
                            interpret=self.interpret, impl=self.impl)
                    self._charge_chunk(pool, counters)
                else:
                    # a pool pins lanes x Mp x Np of device memory;
                    # traffic whose shape never recurs must not pin it
                    # forever
                    pool.idle_steps += 1
                    if (self.pool_idle_ttl is not None
                            and pool.idle_steps > self.pool_idle_ttl):
                        del self._pools[bucket]
        self._steps += 1
        with ph.phase("serve.upkeep"):
            self._snapshot_occupancy()
            self._operational_round()
        return completed

    def _on_alert(self, alert) -> None:
        """SLO alert routing beyond the monitor's own (registry +
        tracer): note the transition in the black box and freeze it the
        moment an alert fires — the capture holds the rounds that led
        up to the breach."""
        fl = self.obs.flight
        fl.note("alert", slo=alert.name, state=alert.state,
                burn=alert.burn_fast)
        if alert.state == "firing":
            fl.dump(f"alert:{alert.name}", reason=alert.describe())

    def _operational_round(self) -> None:
        """Per-round operational-plane upkeep: close the flight
        recorder's round, tick the rolling windows, evaluate SLO burn
        rates. All three are null twins under obs=False."""
        obs = self.obs
        if obs.flight.enabled:
            obs.flight.record_round(
                self._steps, queued=len(self._queue),
                in_flight=self.in_flight,
                occupancy=self._g_occupancy.value,
                deadline_misses=self._c["deadline_misses"].value)
        if (self._steps % self.op_interval == 0
                or (not self.in_flight and not self.pending)):
            obs.windows.tick()
            obs.slo.evaluate()

    def run(self, max_steps: int | None = None) -> dict[int, np.ndarray]:
        """Step until queue and lanes drain (or ``max_steps`` *additional*
        steps ran); returns all completions."""
        start = self._steps
        out: dict[int, np.ndarray] = {}
        while self.pending or self.in_flight:
            out.update(self.step())
            if max_steps is not None and self._steps - start >= max_steps:
                break
        out.update(self._evict_finished())  # final chunk's completions
        return out

    # ---- internals --------------------------------------------------------

    def _charge_chunk(self, pool, counters: dict) -> None:
        """Charge one chunk advance's modeled HBM bytes to the traffic
        accountant and fold the pool's ``impl='auto'`` routing into the
        registry dispatch counters. With an explicit (non-auto) impl the
        stepped path makes no routing decision — the streamed formula
        applies (the resident chunk only runs via auto/resident routing).
        """
        for k, v in counters.items():
            if v:
                self._c_dispatch[k].inc(v)
        if not self.obs.traffic.enabled:
            return
        tier = ("resident" if counters["resident"] > 0 else "streamed")
        Mb, Nb = pool.bucket
        self.obs.traffic.charge_chunk(
            route="lane", tier=tier, L=pool.num_lanes, M=Mb, N=Nb,
            s=jnp.dtype(pool.state.P.dtype).itemsize,
            chunk_iters=self.chunk_iters)

    def _request_kernel(self, req: ScheduledRequest) -> np.ndarray:
        """The request's (M, N) coupling matrix for an off-lane re-solve:
        the stored payload for dense requests, the geometry's Gibbs mirror
        for coordinate requests."""
        if req.K is not None:
            return req.K
        g = PointCloudGeometry(
            x=jnp.asarray(req.x), y=jnp.asarray(req.y),
            xn=jnp.asarray(req.xn), yn=jnp.asarray(req.yn),
            scale=req.scale)
        return np.asarray(g.kernel(self.cfg.reg))

    def _escalate(self, req: ScheduledRequest):
        """One log-domain retry of a quarantined request. Returns
        ``(P or None, iters)`` — P non-None iff the escalated solve
        produced an all-finite coupling. The retry runs synchronously at
        eviction (the robust tier is the slow path; a poisoned request is
        rare by construction, so blocking the round is the simple-and-
        correct choice — noted in ROADMAP as a possible async follow-up).
        """
        if not self.retry_escalate or req.retries >= 1:
            return None, 0
        req.retries += 1
        P, stats, ok = escalate_log_solve(
            self._request_kernel(req), req.a, req.b, self.cfg,
            factor=self.escalate_factor)
        return (P if ok else None), stats["iters"]

    def _trim_results(self) -> None:
        # the poll pickup store is bounded (oldest dropped) —
        # step()/run() return values are the primary delivery. An
        # un-polled coupling that falls off the bound leaves a 'lost'
        # tombstone so the client can still distinguish "pending" from
        # "gone" (the disposition store is O(1) per request, not O(M*N),
        # so IT is not what the bound protects).
        while len(self._results) > self.max_results:
            old = next(iter(self._results))
            self._results.pop(old)
            self._c["lost_results"].inc()
            self.obs.tracer.emit(old, "lost")
            self._store_disposition(RequestFailure(
                rid=old, status="lost",
                reason="coupling evicted from the bounded result store "
                       "(max_results) before it was polled"))

    def _evict_finished(self) -> dict[int, np.ndarray]:
        completed: dict[int, np.ndarray] = {}
        now = self.clock()
        tr = self.obs.tracer
        for pool in self._pools.values():
            if not pool.requests:
                continue
            iters = np.asarray(pool.state.iters)
            conv = np.asarray(pool.state.converged)
            healthy = np.asarray(pool.state.healthy)
            if tr.enabled:
                # per-request chunk progress, from the host copies this
                # eviction pass already fetched — no extra device sync
                for l, req in pool.requests.items():
                    tr.emit(req.rid, "chunk", lane=l, device=-1,
                            iters=int(iters[l]), converged=bool(conv[l]),
                            healthy=bool(healthy[l]))
            # a degraded request finishes at its reduced budget, not the
            # global cap (the budget is enforced at chunk granularity —
            # the device gate still runs lanes toward cfg.num_iters); an
            # unhealthy lane is finished the moment its flag clears
            finished = [
                l for l, req in list(pool.requests.items())
                if not healthy[l] or conv[l] or iters[l] >= (
                    req.max_iters if req.max_iters is not None
                    else self.cfg.num_iters)]
            if not finished:
                continue
            for lane in finished:
                req = pool.requests.pop(lane)
                admitted = pool.admitted_at.pop(lane)
                M, N = req.shape
                P = None
                if healthy[lane]:
                    # slice per lane on device (one jit signature per lane
                    # index) so only the finished lane crosses to the
                    # host, then trim to the request shape in numpy — not
                    # the whole pool, no per-(lane, shape) compile jitter,
                    # and a copy so the retained result doesn't pin the
                    # padded lane buffer
                    with self.obs.phases.phase("serve.evict.read"):
                        P = np.asarray(pool.state.P[lane])[:M, :N].copy()
                        # second line of defense, O(M*N) on the one
                        # evicted slice only: poison that lands AFTER the
                        # convergence latch froze the lane (e.g. injected
                        # state corruption) never passes through the
                        # detector's frow/colsum window — catch it on the
                        # way out
                        if not np.all(np.isfinite(P)):
                            P = None
                n_iters = int(iters[lane])
                tr.emit(req.rid, "evict", lane=lane, device=-1,
                        iters=n_iters, converged=bool(conv[lane]),
                        healthy=bool(healthy[lane] and P is not None))
                if P is not None:
                    timed_out = (self.cfg.tol is not None
                                 and not conv[lane]
                                 and req.max_iters is None)
                    status = "timed_out" if timed_out else "ok"
                    self._c["timed_out"].inc(int(timed_out))
                else:
                    self._c["unhealthy_evictions"].inc()
                    self.obs.flight.note("unhealthy", rid=req.rid,
                                         lane=lane)
                    tr.emit(req.rid, "escalate", retries=req.retries + 1)
                    P, n_iters = self._escalate(req)
                    status = "retried_ok" if P is not None else "failed"
                if P is not None:
                    if status == "retried_ok":
                        self._c["retried_ok"].inc()
                    completed[req.rid] = self._results[req.rid] = P
                    self._trim_results()
                else:
                    self._c["failed"].inc()
                    self._store_disposition(RequestFailure(
                        rid=req.rid, status="failed",
                        reason="lane state went non-finite and the "
                               "log-domain escalation did not recover",
                        retries=req.retries))
                rec = RequestTelemetry(
                    rid=req.rid, bucket=pool.bucket, lane=lane,
                    arrival=req.arrival, admitted=admitted,
                    completed=now, iters=n_iters,
                    converged=bool(conv[lane] & healthy[lane]),
                    deadline=req.deadline, shed=req.shed,
                    status=status, retries=req.retries,
                    degrade_level=req.degrade_level,
                    est_error=req.est_error,
                    predicted_iters=req.predicted_iters)
                if rec.deadline is not None:
                    self._c["deadlined_completed"].inc()
                    self._c["deadline_misses"].inc(int(rec.missed))
                self._c["completed"].inc()
                self._h_wait.observe(rec.wait)
                self._h_latency.observe(rec.latency)
                self._h_iters.observe(n_iters)
                if (self.predictive and n_iters > 0
                        and status in ("ok", "timed_out")
                        and req.max_iters is None):
                    # close the control loop: feed the predictor the
                    # actual count (full solves only — truncated budgets
                    # would bias the model), refine the online
                    # seconds-per-iteration rate, and record the
                    # prediction's relative error for auditing
                    self.predictor.observe(
                        self.cfg, n_iters, bucket=pool.bucket,
                        mass_a=float(req.a.sum()),
                        mass_b=float(req.b.sum()))
                    a_ = 0.25
                    self._iters_ewma = (
                        n_iters if self._iters_ewma is None
                        else self._iters_ewma + a_ * (n_iters
                                                      - self._iters_ewma))
                    dt = (now - admitted) / n_iters
                    if dt > 0.0:
                        self._spi_ewma = (
                            dt if self._spi_ewma is None
                            else self._spi_ewma + a_ * (dt - self._spi_ewma))
                    if req.predicted_iters:
                        self._h_pred_err.observe(
                            abs(req.predicted_iters - n_iters) / n_iters)
                tr.emit(req.rid, "complete", status=status, iters=n_iters,
                        retries=req.retries)
                self._log_request(rec)
            # one pool update for the whole round's evictions; the index
            # vector is padded to the pool size with duplicates (same
            # zeroing either way) so there is ONE jit signature per pool,
            # not one per eviction count — and eviction's zeroing is also
            # what scrubs a poisoned lane's NaNs out of the pool
            lanes = finished + [finished[-1]] * (pool.num_lanes
                                                 - len(finished))
            pool.state = ops.lane_evict(pool.state,
                                        jnp.asarray(lanes, jnp.int32))
        return completed

    def inject_lane_fault(self, rid: int) -> bool:
        """Chaos/drill hook: corrupt the in-flight lane currently holding
        ``rid`` with NaN state (tile + factors), simulating device-memory
        poisoning mid-solve — the host-side payload stays intact, so the
        quarantine-and-retry path can recover the request on the
        log-domain tier (``status='retried_ok'``). Returns False when the
        rid is not in a lane (queued / already finished). Test
        infrastructure — never called by the serving loop itself."""
        for pool in self._pools.values():
            for lane, req in pool.requests.items():
                if req.rid == rid:
                    st = pool.state
                    pool.state = dataclasses.replace(
                        st,
                        P=st.P.at[lane].set(
                            jnp.asarray(jnp.nan, st.P.dtype)),
                        colsum=st.colsum.at[lane].set(jnp.nan),
                        frow=st.frow.at[lane].set(jnp.nan))
                    return True
        return False

    def _shed_at_admission(self, req: ScheduledRequest, now: float) -> bool:
        """Apply the shed policy to a request whose deadline already
        passed; returns True when the request was dropped entirely."""
        if (self.shed_policy == "none" or req.deadline is None
                or now <= req.deadline):
            return False
        if self.shed_policy == "drop":
            self._c["shed_dropped"].inc()
            self._c["rejected"].inc()
            self._log_request(RequestTelemetry(
                rid=req.rid, bucket=req.bucket, lane=-1,
                arrival=req.arrival, admitted=now, completed=now,
                iters=0, converged=False, deadline=req.deadline,
                shed="dropped", status="rejected"))
            self.obs.tracer.emit(req.rid, "shed", policy="drop")
            self.obs.flight.note("shed", rid=req.rid, policy="drop")
            self.obs.tracer.emit(req.rid, "complete", status="rejected",
                                 reason="deadline passed at admission "
                                        "(shed_policy='drop')")
            # a dropped request must still resolve at poll() — 'rejected'
            # disposition, never silently absent
            self._store_disposition(RequestFailure(
                rid=req.rid, status="rejected",
                reason="deadline already passed at admission "
                       "(shed_policy='drop')"))
            return True
        # 'degrade': an expired deadline walks the ladder — level 1
        # normally, deeper when the brownout controller says the whole
        # system is already shedding accuracy
        self.obs.tracer.emit(req.rid, "shed", policy="degrade")
        level = max(1, self.brownout.level if self.brownout else 0)
        self._degrade(req, level)
        return False

    def _degrade_if_infeasible(self, req: ScheduledRequest,
                               now: float) -> None:
        """Re-judge feasibility against the REMAINING deadline budget at
        admission time — the submit-time gate cannot see queue wait. A
        full solve that no longer fits degrades to the shallowest level
        that does (level 1's service is the ``degrade_iters`` budget,
        else the deepest level the request supports), so every request
        still served at ``degrade_level == 0`` was feasibility-clean at
        BOTH judgment points: the no-SLO-miss-among-full-quality
        property the overload bench hard-asserts. Active only under
        shed_policy='degrade' with a calibrated model; expired deadlines
        are ``_shed_at_admission``'s job."""
        if (self.shed_policy != "degrade" or not self.predictive
                or req.deadline is None or req.degrade_level > 0):
            return
        spi = self._seconds_per_iter()
        service = self._predicted_service(req)
        if spi is None or service is None:
            return
        if now + self.feasibility_margin * service <= req.deadline:
            return
        lvl1 = min(self.cfg.num_iters, self.degrade_iters) * spi
        level = (1 if now + self.feasibility_margin * lvl1 <= req.deadline
                 else self.max_degrade_level(req))
        self._c_infeasible.inc()
        self.obs.tracer.emit(req.rid, "shed", policy="infeasible_wait",
                             level=level)
        self._degrade(req, level)

    def _admit_queued(self) -> None:
        if not self._queue:
            return
        now = self.clock()
        remaining: list[ScheduledRequest] = []
        placements: dict[tuple[int, int], list[tuple[int, ScheduledRequest]]]
        placements = {}
        # predicted-finish-time EDF: with a calibrated service-time model
        # the queue orders by least slack (deadline minus predicted
        # service) — a long job with a near deadline outranks a short job
        # with the same deadline; uncalibrated, this is exactly edf_key
        if self.predictive and self._seconds_per_iter() is not None:
            def admit_key(r):
                return r.slack_key(self._predicted_service(r))
        else:
            admit_key = ScheduledRequest.edf_key
        brownout_level = (self.brownout.level
                          if (self.brownout is not None
                              and self.shed_policy == "degrade") else 0)
        for req in sorted(self._queue, key=admit_key):
            if req.shed is None and self._shed_at_admission(req, now):
                continue                  # dropped: telemetry only, no lane
            self._degrade_if_infeasible(req, now)
            if brownout_level:
                # sustained overload: new admissions shed accuracy so the
                # backlog drains faster than it grows
                self._degrade(req, brownout_level)
            if req.degrade_level >= 2 and req.K is None:
                # level 2: solve NOW on the host sliced tier — never
                # occupies a lane, returns this same scheduling round
                self._complete_sliced(req, now)
                continue
            pool = self._pools.get(req.bucket)
            if pool is None:
                pool = self._pools[req.bucket] = _LanePool(
                    req.bucket, self.lanes_per_pool, self.cfg,
                    storage_dtype=self.storage_dtype)
            free = pool.free_lanes()
            if not free:
                remaining.append(req)
                continue
            lane = free[0]
            placements.setdefault(req.bucket, []).append((lane, req))
            pool.requests[lane] = req
            pool.admitted_at[lane] = now
            self.obs.flight.note("place", rid=req.rid, lane=lane)
            self.obs.tracer.emit(req.rid, "place", lane=lane, device=-1,
                                 bucket=list(req.bucket), route="lane")
        for bucket, placed in placements.items():
            # Normalize to the bucket shape host-side (numpy) so lane_admit
            # never traces per request shape, and land a round's admissions
            # in as few pool updates as possible. Each group's batch is
            # padded to the pool size by repeating the last admission
            # (duplicate scatter indices with identical payloads are
            # harmless), so each pool compiles ONE admit signature per
            # payload kind — not one per admission count. Dense requests
            # ship their K; point requests ship coordinates + norms
            # ((M + N) * (d + 1) floats) and materialize K on-device,
            # grouped by (d, scale) since those shape/brand the
            # materializer.
            dense = [(l, r) for l, r in placed if r.K is not None]
            points: dict[tuple[int, float], list] = {}
            for l, r in placed:
                if r.K is None:
                    points.setdefault((r.x.shape[1], r.scale),
                                      []).append((l, r))
            if dense:
                self._admit_dense(bucket, dense)
            for (d, scale), group in points.items():
                self._admit_points(bucket, group, d, scale)
        # EDF order (which already ends in the rid FIFO tie-break) is
        # recomputed from scratch next round, so storage order is free.
        self._queue = remaining

    def _admit_dense(self, bucket, placed) -> None:
        pool = self._pools[bucket]
        Mb, Nb = bucket
        L = pool.num_lanes
        f32, i32, (Kp, ap, bp), (lanes,) = ops.admit_buffers(L, Mb, Nb)
        for j in range(L):
            lane, req = placed[min(j, len(placed) - 1)]
            M, N = req.shape
            Kp[j, :M, :N] = req.K
            ap[j, :M] = req.a
            bp[j, :N] = req.b
            lanes[j] = lane
        self.obs.traffic.charge_admission(
            route="lane", M=Mb, N=Nb, s=4, source="dense",
            count=len(placed))
        self._launch_admit(pool, f32, i32, bucket=bucket)

    def _admit_points(self, bucket, placed, d: int, scale: float) -> None:
        """Admit a round's point-cloud requests: ship coordinates and
        norms, and materialize the masked Gibbs stack on-device inside
        the admission launch (the geometry mirror's arithmetic, so lanes
        are bit-identical to dense submission of
        ``geometry.kernel(cfg.reg)``), one pool update."""
        pool = self._pools[bucket]
        Mb, Nb = bucket
        L = pool.num_lanes
        f32, i32, (xs, xns, ys, yns, ap, bp), (lanes, mv, nv) = \
            ops.admit_buffers(L, Mb, Nb, d)
        for j in range(L):
            lane, req = placed[min(j, len(placed) - 1)]
            M, N = req.shape
            xs[j, :M], xns[j, :M] = req.x, req.xn
            ys[j, :N], yns[j, :N] = req.y, req.yn
            mv[j], nv[j] = M, N
            ap[j, :M] = req.a
            bp[j, :N] = req.b
            lanes[j] = lane
        self.obs.traffic.charge_admission(
            route="lane", M=Mb, N=Nb, s=4, source="implicit", d=d,
            count=len(placed))
        self._launch_admit(pool, f32, i32, bucket=bucket, d=d,
                           reg=float(self.cfg.reg), scale=scale)

    def _launch_admit(self, pool: _LanePool, f32: np.ndarray,
                      i32: np.ndarray, **static) -> None:
        """One pool update: the two packed host buffers cross to the
        device and one launch writes them into the donated pool."""
        with self.obs.phases.phase("serve.admit.launch"):
            pool.state = ops.lane_admit_packed(pool.state, f32, i32,
                                               **static)
        self._c_admit_updates.inc()
        self._c_admit_calls.inc(2 + 1)   # two transfers, one launch

    def _snapshot_occupancy(self) -> None:
        occ = {str(b): p.occupancy for b, p in self._pools.items()}
        self.occupancy_log.append({
            "step": self._steps,
            "queued": len(self._queue),
            "deadline_misses": self._c["deadline_misses"].value,  # running
            "pools": occ,
        })
        self._g_queued.set(len(self._queue))
        self._g_in_flight.set(self.in_flight)
        self._g_occupancy.set(sum(occ.values()) / len(occ) if occ else 0.0)
        # the bounded telemetry window silently narrows what stats()'s
        # latency/p99 aggregates describe — count what falls off so the
        # truncation is visible (stats()['window_dropped'] + registry).
        # Request records trim at append time (_log_request — every
        # producer path, including shed-drops and submit-time rejects);
        # the occupancy window has exactly one producer, here.
        self._c["window_dropped_occupancy"].inc(
            max(0, len(self.occupancy_log) - self.max_log))
        del self.occupancy_log[:-self.max_log]

    # ---- telemetry --------------------------------------------------------

    def stats(self) -> dict:
        """Aggregate serving telemetry over the retained log window
        (the last ``max_log`` completions / occupancy snapshots).
        ``deadline_misses`` / ``miss_rate`` are *running* totals over every
        completion (misses / completions-that-had-deadlines), so they stay
        correct after the window trims; ``window_dropped`` counts what the
        trims discarded, so the narrowing itself is visible. The running
        totals are registry counters (``serve.*`` in ``self.obs.registry``
        — see ``repro.serve``'s Observability section for the mapping)."""
        c = self._c
        misses = {
            "deadline_misses": c["deadline_misses"].value,
            "miss_rate": (c["deadline_misses"].value
                          / c["deadlined_completed"].value
                          if c["deadlined_completed"].value else 0.0),
            # running shed totals (drop: refused a lane at admission;
            # degrade: admitted with the reduced iteration budget)
            "shed_dropped": c["shed_dropped"].value,
            "shed_degraded": c["shed_degraded"].value,
            # running fault-containment totals (exact; survive trimming)
            "rejected": c["rejected"].value,
            "failed": c["failed"].value,
            "retried_ok": c["retried_ok"].value,
            "timed_out": c["timed_out"].value,
            "unhealthy_evictions": c["unhealthy_evictions"].value,
            "lost_results": c["lost_results"].value,
            "window_dropped": {
                "requests": c["window_dropped_requests"].value,
                "occupancy": c["window_dropped_occupancy"].value,
                "dispositions": c["window_dropped_dispositions"].value,
            },
            # overload-model totals (predictive admission + degrade
            # ladder; zeros when the features are off)
            "admission_infeasible": self._c_infeasible.value,
            "degrade_levels": {lvl: ctr.value
                               for lvl, ctr in self._c_degrade.items()},
            "brownout_level": (self.brownout.level
                               if self.brownout is not None else 0),
            "seconds_per_iter": self._seconds_per_iter(),
        }
        status_counts: dict[str, int] = {}
        for t in self.request_log:
            status_counts[t.status] = status_counts.get(t.status, 0) + 1
        misses["status_counts"] = status_counts
        # dropped and admission-rejected requests never solved anything:
        # they appear in the log (lane=-1) but are excluded from the
        # latency / iteration aggregates, which describe served work
        served = [t for t in self.request_log
                  if t.shed != "dropped" and t.status != "rejected"]
        if not served:
            return {"completed": 0, "steps": self._steps, "wait_mean": 0.0,
                    "wait_p99": 0.0, "latency_p50": 0.0, "latency_p99": 0.0,
                    "iters_mean": 0.0, "iters_max": 0,
                    "converged_frac": 0.0, "occupancy_mean": 0.0, **misses}
        waits = np.array([t.wait for t in served])
        lats = np.array([t.latency for t in served])
        iters = np.array([t.iters for t in served])
        occ = [o for snap in self.occupancy_log
               for o in snap["pools"].values()]
        return {
            "completed": len(served),
            "steps": self._steps,
            "wait_mean": float(waits.mean()),
            "wait_p99": float(np.percentile(waits, 99)),
            "latency_p50": float(np.percentile(lats, 50)),
            "latency_p99": float(np.percentile(lats, 99)),
            "iters_mean": float(iters.mean()),
            "iters_max": int(iters.max()),
            "converged_frac": float(np.mean([t.converged for t in served])),
            "occupancy_mean": float(np.mean(occ)) if occ else 0.0,
            **misses,
        }
