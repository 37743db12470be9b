"""Multi-device UOT serving: a request router over sharded lane pools.

``ClusterScheduler`` is the fourth serving tier (see ``repro.serve``'s
ladder): ``UOTScheduler``'s continuous batching, scaled from one device's
lane pool to every device in a mesh, plus an escape hatch into the
row-sharded gang solvers for problems no lane pool can hold. One submit
API covers the whole range — a request is never rejected for its shape.

Architecture, in the order a request experiences it:

* **routing** — ``submit`` classifies by padded bucket shape: problems
  within the lane-pool budget join the (global, EDF-ordered) lane queue;
  over-budget problems join the gang queue and run on
  ``core.distributed.gang_solve`` (the paper's Tianhe-1 row-sharded
  design) instead of being refused. ``submit_points`` ships coordinate
  payloads — O((M+N)*(d+1)) floats, so routing them to ANY device shard
  costs the same handful of bytes; the Gibbs kernel materializes on-device
  at admission exactly as in the single-device scheduler.
* **placement** — at admission the router picks a device shard for each
  request: ``placement='least_loaded'`` balances active lanes across the
  mesh; ``'bucket_affinity'`` packs a bucket's traffic onto the devices
  already serving it (fewer pools per device, warmer reuse), spilling
  least-loaded when the affinity set is full. With ``share_pools=True``
  the affinity path may drop a request into a *wider* existing pool using
  per-lane ``m_valid``/``n_valid`` masking (cross-bucket lane sharing) —
  zero-padding is exact, so the answer is bit-identical either way.
  Placement cannot change results — per-lane math is placement-invariant
  (property-tested) — only latency and memory layout.
* **advance** — each bucket's ``ClusterLaneState`` pool stack advances ALL
  devices' lanes in one ``shard_map``-ped chunk launch
  (``cluster_stepped``); between chunks finished lanes are evicted
  (results returned immediately) and freed slots refilled EDF, exactly the
  single-device loop but with (device, lane) slots.
* **backpressure** — cluster-wide: ``max_queue`` waiting requests raise
  ``QueueFullError``. Per-device: a device at ``device_active_cap`` (or
  with no free lane) refuses placements and the router spills or leaves
  the request queued (``router['placement_stalls']``), so one hot device
  sheds load to the rest of the mesh instead of queueing it privately.
* **telemetry** — per-request ``ClusterRequestTelemetry`` (device + route
  on top of the single-device record), per-device placement/completion
  counters and occupancy, router decision counts, and the scheduler's own
  ``impl='auto'`` dispatch decisions (via ``ops.dispatch_counters`` — the
  per-context counters, so concurrent schedulers don't clobber each
  other) — all rolled up in ``stats()``.

The async double-buffered step loop (``step_mode='async'``): a scheduling
round's *decision-free* host work — EDF presort and payload padding for the
next admissions — runs while the previous chunk is still executing on the
devices, and the ``jax.block_until_ready`` barrier of the sync loop is
deferred to the moment eviction actually reads the chunk's lifecycle flags.
Decisions consume exactly the values the sync loop consumes, so results
and iteration counts are bit-identical between the modes (tested); only
wall-clock overlap differs. ``step_mode='sync'`` is the fallback that
blocks right after each dispatch.

Bit-identity contract (the acceptance property): for any trace, every
request's coupling equals — bit for bit — what a single-device
``UOTScheduler`` returns for the same problem, whatever the placement,
arrival order, chunk interleaving, device count, or step mode
(tests/test_cluster.py in-process, tests/_cluster_check.py on 8 forced
host devices).

Fault containment (on top of ``UOTScheduler``'s ladder — admission
validation, lane-health detection, typed dispositions, chaos hook — all
inherited with the same semantics):

* **device quarantine** — the blackout signature is *every* active lane
  of a device (>= 2 of them) unhealthy in the same round: that is not a
  bad payload, it is bad HARDWARE state (HBM/interconnect corruption of
  one shard — the ``cluster_poison_device`` fault model). The device is
  quarantined: drained (its in-flight requests leave their lanes),
  excluded from all future placement, and surfaced as
  ``stats()['device_health']``. Quarantine is one-way — returning a
  flapping device to service is an operator decision, not a scheduler
  heuristic.
* **drain = requeue-first** — a drained (or individually poisoned)
  request whose host-side payload is intact simply goes back in the
  admission queue (``retries`` 0 -> 1) and lands on a healthy device,
  where its fresh lane solve is bit-identical to the fault-free answer
  (``status='ok'``, ``retries=1``). Only a SECOND corruption of the same
  request escalates to the log-domain tier
  (``status='retried_ok'``/'failed') — so transient device faults cost a
  bounce, not a semantics change, and a poisonous payload (NaN kernel)
  cannot ping-pong between devices forever.
* **all-quarantined fallback** — if no healthy device shard remains, the
  lane queue drains into the gang path (``gang='auto'``), which solves
  per request without lane pools; serving capacity degrades, requests
  still resolve.
* **gang wall-clock timeout** — ``gang_timeout=`` bounds the gang tier's
  latency at solve granularity (a fused launch cannot be preempted
  mid-flight): a breaching solve still delivers its coupling but is
  recorded ``status='timed_out'``, and subsequent gang solves run the
  degraded ``degrade_iters`` budget — coarse answers at bounded latency,
  the ``shed_policy='degrade'`` contract applied to the gang. The gang
  mesh itself is NOT narrowed by quarantine: the blackout model poisons
  lane-pool *state*, which gang solves never read.

Overload model (``predictive=True``; the ``UOTScheduler`` semantics —
see ``repro.serve``'s overload model section — applied to the LANE
route): SLO-feasibility admission (``InfeasibleDeadline`` under
``shed_policy='drop'``, immediate ladder walk under ``'degrade'``),
least-slack admission ordering once the cluster-wide service-time model
calibrates, a brownout controller on total backlog over healthy lane
capacity, and the degrade ladder ending in the host-side sliced 1-D
tier (``route='sliced'``, never occupies a (device, lane) slot). The
feasibility gate never judges gang-routed requests — the lane-
calibrated model does not describe row-sharded gang solves; the gang
tier keeps its latched ``gang_timeout`` degradation instead. A point
request the ladder walked to level 2 is taken by the sliced tier from
EITHER queue (it is route-independent and cheaper than any launch).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obslib
from repro.core.problem import UOTConfig
from repro.core import distributed
from repro.core.health import (InvalidProblemError, escalate_log_solve,
                               validate_problem)
from repro.core.predict import (IterPredictor, estimate_truncation_error,
                                measured_seconds_per_iter)
from repro.geometry import PointCloudGeometry
from repro.geometry.sliced import lift_coupling_np, sliced_uot
from repro.kernels import ops
from repro.serve.overload import (BrownoutController, InfeasibleDeadline,
                                  queue_pressure)
from repro.serve.scheduler import (_COUNTER_NAMES, QueueFullError,
                                   RequestFailure, RequestTelemetry,
                                   ScheduledRequest)
from repro.cluster.lanes import (ClusterLaneState, cluster_admit,
                                 cluster_done, cluster_evict,
                                 cluster_poison_device, cluster_stepped,
                                 make_cluster_lane_state, stack_get,
                                 stack_set)


@dataclasses.dataclass
class ClusterRequestTelemetry(RequestTelemetry):
    """Per-request record with the cluster placement on top: which device
    shard served the lanes (-1 for gang/sliced/dropped requests) and which
    route the request took ('lane', 'gang', 'sliced' — the level-2
    degrade tier, solved host-side off any lane — or 'dropped')."""

    device: int = -1
    route: str = "lane"


class _ClusterPool:
    """One bucket's device-stacked lane pools + host-side bookkeeping.

    ``requests`` / ``admitted_at`` are keyed by (device, lane) slots. The
    pool may be *wider* than a resident request's own bucket when the
    router shares pools cross-bucket — per-slot valid extents live in the
    device state (``m_valid``/``n_valid``) and in each request's shape.
    """

    def __init__(self, bucket: tuple[int, int], num_devices: int,
                 lanes_per_device: int, cfg: UOTConfig, *, mesh, axis,
                 storage_dtype=None):
        self.bucket = bucket
        self.cfg = cfg
        self.state = make_cluster_lane_state(
            num_devices, lanes_per_device, bucket[0], bucket[1], cfg,
            mesh=mesh, axis=axis, storage_dtype=storage_dtype)
        self.requests: dict[tuple[int, int], ScheduledRequest] = {}
        self.admitted_at: dict[tuple[int, int], float] = {}
        self.idle_steps = 0

    @property
    def num_devices(self) -> int:
        return self.state.num_devices

    @property
    def lanes_per_device(self) -> int:
        return self.state.lanes_per_device

    def free_lanes(self, device: int) -> list[int]:
        return [l for l in range(self.lanes_per_device)
                if (device, l) not in self.requests]

    def device_active(self, device: int) -> int:
        return sum(1 for d, _ in self.requests if d == device)

    @property
    def occupancy(self) -> float:
        return len(self.requests) / (self.num_devices
                                     * self.lanes_per_device)

    def per_device_occupancy(self) -> list[float]:
        return [self.device_active(d) / self.lanes_per_device
                for d in range(self.num_devices)]


class ClusterScheduler:
    """Deadline-aware continuous batching across a device mesh.

    Usage::

        mesh = cluster_mesh()                      # all local devices
        sched = ClusterScheduler(UOTConfig(num_iters=100, tol=1e-4),
                                 mesh=mesh, lanes_per_device=8)
        rid = sched.submit(K, a, b, deadline=now + 0.5)
        big = sched.submit(K_huge, a2, b2)         # -> row-sharded gang
        results = sched.run()                      # {rid: coupling}

    Without a mesh (``num_devices=`` instead) the device axis is simulated
    with per-device launches — same results, no shard_map — which is the
    1-chip fallback and the oracle the mesh path is tested against.

    Constructor knobs beyond ``UOTScheduler``'s: ``placement``
    ('least_loaded' | 'bucket_affinity'), ``share_pools`` (cross-bucket
    lane sharing on the affinity path), ``device_active_cap`` (per-device
    admission ceiling), ``step_mode`` ('sync' | 'async' double-buffered
    loop), and the gang escape hatch (``gang='auto'`` routes lane-budget
    failures to ``core.distributed.gang_solve``; ``lane_budget`` overrides
    the predicate, default ``ops.resident_fits`` on the bucket shape;
    ``gang_per_step`` bounds how many gang solves one round runs).
    """

    def __init__(self, cfg: UOTConfig, *, mesh=None, axis: str = "devices",
                 num_devices: int | None = None, lanes_per_device: int = 8,
                 chunk_iters: int = 4, max_queue: int = 1024,
                 m_bucket: int = 64, n_bucket: int = 128,
                 storage_dtype=None, interpret: bool | None = None,
                 impl: str | None = None, max_log: int = 10_000,
                 max_results: int = 256, pool_idle_ttl: int | None = 100,
                 shed_policy: str = "none", degrade_iters: int | None = None,
                 placement: str = "least_loaded", share_pools: bool = False,
                 device_active_cap: int | None = None,
                 step_mode: str = "sync", gang: str = "auto",
                 gang_per_step: int = 1, gang_overlapped: bool = False,
                 gang_timeout: float | None = None,
                 lane_budget: Callable[[int, int], bool] | None = None,
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
        if lanes_per_device < 1:
            raise ValueError("lanes_per_device must be >= 1")
        if chunk_iters < 1:
            raise ValueError("chunk_iters must be >= 1")
        if placement not in ("least_loaded", "bucket_affinity"):
            raise ValueError(f"placement must be 'least_loaded' or "
                             f"'bucket_affinity', got {placement!r}")
        if step_mode not in ("sync", "async"):
            raise ValueError(f"step_mode must be 'sync' or 'async', "
                             f"got {step_mode!r}")
        if shed_policy not in ("none", "drop", "degrade"):
            raise ValueError(f"shed_policy must be 'none', 'drop' or "
                             f"'degrade', got {shed_policy!r}")
        if gang not in ("auto", "never"):
            raise ValueError(f"gang must be 'auto' or 'never', got {gang!r}")
        if share_pools and placement != "bucket_affinity":
            # documented scope: cross-bucket sharing is an affinity-path
            # feature (full generalization is a ROADMAP item) — refuse
            # loudly rather than silently sharing under another policy
            raise ValueError("share_pools requires "
                             "placement='bucket_affinity'")
        if mesh is not None:
            if axis not in mesh.shape:
                raise ValueError(f"mesh has no axis {axis!r}")
            mesh_n = mesh.shape[axis]
            if num_devices is not None and num_devices != mesh_n:
                raise ValueError(f"num_devices={num_devices} != mesh axis "
                                 f"size {mesh_n}")
            num_devices = mesh_n
        self.cfg = cfg
        self.mesh = mesh
        self.axis = axis
        self.num_devices = num_devices or 1
        self.lanes_per_device = lanes_per_device
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
        self.shed_policy = shed_policy
        self.degrade_iters = (chunk_iters if degrade_iters is None
                              else degrade_iters)
        self.placement = placement
        self.share_pools = share_pools
        self.device_active_cap = device_active_cap
        self.step_mode = step_mode
        self.gang = gang
        self.gang_per_step = gang_per_step
        self.gang_overlapped = gang_overlapped
        self.gang_timeout = gang_timeout
        # Fault containment (same knobs as UOTScheduler): typed admission
        # validation, the log-domain escalation gate for twice-corrupted
        # requests, and the chaos hook (repro.serve.faults).
        self.validate = validate
        self.retry_escalate = retry_escalate
        self.escalate_factor = escalate_factor
        self.fault_injector = fault_injector
        # Overload model — same semantics as UOTScheduler (see its ctor
        # comment and repro.serve's overload model section): feasibility
        # admission, least-slack EDF, and the degrade ladder on the LANE
        # path. The gang tier keeps its existing expired-shed + latched
        # gang_timeout degradation: the lane-calibrated service-time
        # model does not describe row-sharded gang solves, so the
        # feasibility gate never judges gang-routed requests.
        self.predictive = predictive
        self.feasibility_margin = feasibility_margin
        self.predictor = (predictor if predictor is not None
                          else IterPredictor())
        self.brownout = brownout
        if predictive and brownout is None and shed_policy == "degrade":
            self.brownout = BrownoutController()
        self.sliced_n_proj = sliced_n_proj
        self.sliced_seed = sliced_seed
        self._spi_pinned = seconds_per_iter
        self._spi_ewma: float | None = None
        self._iters_ewma: float | None = None
        # Measured performance (see UOTScheduler's ctor comment): a
        # MeasurementStore feeds the service-time model (pinned >
        # measured > completion EWMA) and makes impl='auto' chunk
        # dispatch measurement-driven via ops.dispatch_advisor.
        self.measurements = measurements
        self._advisor = (obslib.MeasuredDispatch(measurements)
                         if measurements is not None else None)
        self._pending_completed: dict[int, np.ndarray] = {}
        # lane-pool budget: buckets failing it route to the gang. The
        # default is the resident-tier VMEM predicate — a conservative
        # proxy for "small enough to multiplex a lane pool with"; pass
        # your own (Mb, Nb) -> bool to widen or tighten the boundary.
        self._lane_budget = lane_budget or (
            lambda Mb, Nb: ops.resident_fits(
                Mb, Nb, cfg, storage_dtype=storage_dtype))
        self.clock = clock
        self.sleep = sleep
        # Observability bundle (see UOTScheduler / repro.obs): metric
        # names are "cluster.*"; the tracer's place/chunk events carry
        # the device shard, and gang solves get their own span events.
        if obs is None:
            obs = obslib.Observability(clock=clock)
        elif obs is False:
            obs = obslib.Observability(enabled=False, clock=clock,
                                       chain=False)
        self.obs = obs
        # Operational plane (mirrors UOTScheduler): rolling windows,
        # ``slos=`` burn-rate alerting, and the flight recorder, with
        # the cluster's extra dump_on triggers — device quarantine and
        # gang_timeout — wired where those breaches latch.
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
        self._c = {k: reg.counter("cluster." + k)
                   for k in _COUNTER_NAMES + (
                       "requeued", "gang_timeouts", "gang_completed",
                       "devices_quarantined")}
        self._h_wait = reg.histogram("cluster.wait_s")
        self._h_latency = reg.histogram("cluster.latency_s")
        self._h_iters = reg.histogram("cluster.iters",
                                      buckets=obslib.DEFAULT_COUNT_BUCKETS)
        self._g_queued = reg.gauge("cluster.queued")
        self._g_gang_queued = reg.gauge("cluster.gang_queued")
        self._g_in_flight = reg.gauge("cluster.in_flight")
        self._g_occupancy = reg.gauge("cluster.occupancy")

        self._queue: list[ScheduledRequest] = []
        self._gang_queue: list[ScheduledRequest] = []
        self._pools: dict[tuple[int, int], _ClusterPool] = {}
        self._prepped: dict[int, tuple] = {}   # rid -> bucket-padded payload
        self._next_rid = 0
        self._results: dict[int, np.ndarray] = {}
        self._steps = 0
        self.request_log: list[ClusterRequestTelemetry] = []
        self.occupancy_log: list[dict] = []
        # running totals live in ``self._c`` registry counters (exact,
        # survive log trimming, dumped process-wide); the per-device
        # rollup lists and one-way health states stay plain host state
        self._device_placed = [0] * self.num_devices
        self._device_completed = [0] * self.num_devices
        # rid -> RequestFailure, kept apart from the size-bounded coupling
        # store (same rationale as UOTScheduler._dispositions)
        self._dispositions: dict[int, RequestFailure] = {}
        self._gang_degrade = False      # latched by a gang_timeout breach
        # per-device serving state: 'ok' | 'quarantined' (one-way)
        self._device_health = ["ok"] * self.num_devices
        self._router = {k: reg.counter("cluster.router." + k)
                        for k in ("least_loaded", "affinity_hits",
                                  "affinity_spills", "shared_pool",
                                  "placement_stalls", "gang_routed")}
        self._c_dispatch = {k: reg.counter("cluster.dispatch." + k)
                            for k in ("resident", "streamed")}
        # overload-model observability (mirrors "serve.*"; zeros unless
        # predictive admission / the degrade ladder are enabled)
        self._c_infeasible = reg.counter("cluster.admission.infeasible")
        self._c_degrade = {lvl: reg.counter(f"cluster.degrade.l{lvl}")
                           for lvl in (1, 2)}
        self._g_brownout = reg.gauge("cluster.degrade.brownout_level")
        self._h_pred_err = reg.histogram("cluster.predict.rel_err")

    # ---- submission -------------------------------------------------------

    def _check_backpressure(self) -> None:
        depth = len(self._queue) + len(self._gang_queue)
        if depth >= self.max_queue:
            raise QueueFullError(
                f"queue at max_queue={self.max_queue}; retry later",
                queue_depth=depth,
                retry_after=self._retry_after_hint())

    def _log_request(self, rec: ClusterRequestTelemetry) -> None:
        """THE append path for request telemetry: append + trim-and-count
        immediately (see ``UOTScheduler._log_request`` — trimming only at
        the occupancy snapshot missed records appended between steps)."""
        self.request_log.append(rec)
        excess = len(self.request_log) - self.max_log
        if excess > 0:
            self._c["window_dropped_requests"].inc(excess)
            del self.request_log[:excess]

    # ---- service-time model (predictive=True; see UOTScheduler) -----------

    def _healthy_lanes(self) -> int:
        healthy = sum(1 for h in self._device_health if h == "ok")
        return max(1, healthy * self.lanes_per_device)

    def _seconds_per_iter(self, bucket=None) -> float | None:
        """Pinned > measured chunk rate (per-bucket, then aggregate) >
        completion EWMA > None (``UOTScheduler._seconds_per_iter``)."""
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
        spi = self._seconds_per_iter(req.bucket)
        if not self.predictive or spi is None:
            return None
        if req.predicted_iters is None:
            req.predicted_iters = self._predict_request_iters(req)
        return req.predicted_iters * spi

    def _retry_after_hint(self) -> float | None:
        spi = self._seconds_per_iter()
        if (not self.predictive or spi is None
                or self._iters_ewma is None):
            return None
        depth = len(self._queue) + len(self._gang_queue)
        return (depth * self._iters_ewma * spi) / self._healthy_lanes()

    def _feasibility_gate(self, req: ScheduledRequest, now: float,
                          rid: int) -> None:
        """Refuse or degrade a LANE-route request whose SLO is already
        unmeetable (``UOTScheduler._feasibility_gate`` semantics). Gang-
        routed requests are exempt: the lane-calibrated service model
        does not describe row-sharded gang solves."""
        if (not self.predictive or req.deadline is None
                or self.shed_policy == "none"):
            return
        if self.gang == "auto" and not self._lane_budget(*req.bucket):
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
        self._c_infeasible.inc()
        self._degrade(req, self.max_degrade_level(req))

    def _degrade_if_infeasible(self, req: ScheduledRequest,
                               now: float) -> None:
        """Admission-time feasibility re-check against the REMAINING
        deadline budget (``UOTScheduler._degrade_if_infeasible`` — the
        submit-time gate cannot see queue wait). Lane path only: the
        gang queue never reaches this, preserving the gang exemption."""
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

    def max_degrade_level(self, req: ScheduledRequest) -> int:
        """Level 2 (sliced) needs coordinates to project and a finite
        marginal relaxation; dense/balanced requests top out at level 1."""
        return (2 if req.K is None and np.isfinite(self.cfg.reg_m)
                else 1)

    def _degrade(self, req: ScheduledRequest, level: int) -> None:
        """Apply degrade-ladder ``level`` (idempotent upward — see
        ``UOTScheduler._degrade``)."""
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

    def _complete_sliced(self, req: ScheduledRequest, now: float) -> None:
        """Finish a level-2 request on the host sliced tier (no lane, no
        device, no M*N compute) and deliver it this scheduling round via
        the pending buffer — ``UOTScheduler._complete_sliced`` with the
        cluster telemetry record (``device=-1, route='sliced'``)."""
        M, N = req.shape
        res = sliced_uot(req.x, req.y, req.a, req.b,
                         rho=float(self.cfg.reg_m), scale=req.scale,
                         n_proj=self.sliced_n_proj, seed=self.sliced_seed)
        P = lift_coupling_np(res, M, N).astype(np.float32)
        req.est_error = res.est_error
        self._pending_completed[req.rid] = self._results[req.rid] = P
        self._trim_results()
        self._record(ClusterRequestTelemetry(
            rid=req.rid, bucket=req.bucket, lane=-1,
            arrival=req.arrival, admitted=now, completed=now,
            iters=0, converged=True, deadline=req.deadline,
            shed="degraded", status="ok", retries=req.retries,
            degrade_level=2, est_error=res.est_error,
            predicted_iters=req.predicted_iters,
            device=-1, route="sliced"))

    def _route(self, req: ScheduledRequest) -> None:
        """Lane pool or gang, by the lane-pool budget of the bucket."""
        if self.gang == "auto" and not self._lane_budget(*req.bucket):
            self._router["gang_routed"].inc()
            self._gang_queue.append(req)
            self.obs.tracer.emit(req.rid, "queue",
                                 depth=len(self._gang_queue), route="gang")
        else:
            self._queue.append(req)
            self.obs.tracer.emit(req.rid, "queue", depth=len(self._queue),
                                 route="lane")

    def _store_disposition(self, failure: RequestFailure) -> None:
        self._dispositions[failure.rid] = failure
        while len(self._dispositions) > self.max_log:
            self._dispositions.pop(next(iter(self._dispositions)))
            self._c["window_dropped_dispositions"].inc()
        fl = self.obs.flight
        if fl.enabled:
            fl.note("failure", rid=failure.rid, status=failure.status)
            if failure.status == "failed":
                # dump_on RequestFailure (see UOTScheduler)
                fl.dump("request_failure",
                        reason=f"rid {failure.rid}: {failure.reason}")

    def _reject(self, rid: int, bucket, deadline,
                err: InvalidProblemError, now: float) -> None:
        """Refused admission: telemetry + a typed disposition so
        ``poll(rid)`` resolves, then re-raise (rid attached)."""
        self._c["rejected"].inc()
        self._log_request(ClusterRequestTelemetry(
            rid=rid, bucket=bucket, lane=-1, arrival=now, admitted=now,
            completed=now, iters=0, converged=False, deadline=deadline,
            status="rejected", device=-1, route="rejected"))
        self.obs.tracer.emit(rid, "complete", status="rejected",
                             reason=err.reason)
        self._store_disposition(RequestFailure(
            rid=rid, status="rejected", reason=f"{err.reason}: {err}"))
        raise err

    def submit(self, K, a, b, *, deadline: float | None = None,
               priority: int = 0) -> int:
        """Enqueue a problem; returns its request id. Problems too large
        for any lane pool are routed to the row-sharded gang solver
        instead of being rejected (``gang='auto'``); ``QueueFullError``
        applies cluster-wide across both queues. ``InvalidProblemError``
        semantics match ``UOTScheduler.submit``."""
        self._check_backpressure()
        K = np.asarray(K)
        a = np.asarray(a)
        b = np.asarray(b)
        rid = self._next_rid
        self._next_rid += 1
        fault = None
        if self.fault_injector is not None:
            K, a, b, fault = self.fault_injector.on_submit(rid, K, a, b)
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
        self._route(req)
        return rid

    def submit_points(self, x, y, a, b, *, scale: float = 1.0,
                      deadline: float | None = None,
                      priority: int = 0) -> int:
        """Enqueue a point-cloud problem (squared-Euclidean cost of the
        coordinate clouds). The payload is ``(M + N) * (d + 1)`` floats —
        which is what makes coordinate requests cheap to route to ANY
        device shard: the kernel matrix materializes on the owning device
        at admission, bit-identical to dense submission of
        ``geometry.kernel(cfg.reg)`` (single-device contract, inherited)."""
        self._check_backpressure()
        g = PointCloudGeometry.from_points(x, y, scale=scale)
        M, N = g.shape
        a = np.asarray(a)
        b = np.asarray(b)
        rid = self._next_rid
        self._next_rid += 1
        fault = None
        if self.fault_injector is not None:
            _, a, b, fault = self.fault_injector.on_submit(rid, None, a, b)
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
            x=np.asarray(g.x), y=np.asarray(g.y), xn=np.asarray(g.xn),
            yn=np.asarray(g.yn), scale=float(scale), fault=fault)
        self._feasibility_gate(req, now, rid)   # may raise / degrade
        self._route(req)
        return rid

    @property
    def pending(self) -> int:
        """Requests waiting for a lane or a gang slot."""
        return len(self._queue) + len(self._gang_queue)

    @property
    def in_flight(self) -> int:
        """Requests currently occupying lanes."""
        return sum(len(p.requests) for p in self._pools.values())

    def poll(self, rid: int):
        """The terminal disposition of ``rid``: the finished coupling, a
        ``RequestFailure`` (failed / rejected / lost), or None only while
        genuinely pending. Take semantics — handed out exactly once."""
        with self.obs.phases.phase("cluster.poll"):
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
        """One scheduling round: prep -> evict -> admit -> gang -> advance.

        Returns this round's completions ``{rid: P (M, N)}`` (host numpy,
        also retained for ``poll``). In the async double-buffered mode the
        previous round's chunk is typically still running on the devices
        when this round's payload prep executes; the first device-blocking
        read is eviction's lifecycle-flag fetch. The sync mode blocks at
        the end of the round instead, right after dispatch.
        """
        if self.fault_injector is not None:
            self.fault_injector.on_step(self)
        if self.brownout is not None:
            self._g_brownout.set(self.brownout.observe(queue_pressure(
                len(self._queue) + len(self._gang_queue),
                self._healthy_lanes())))
        ph = self.obs.phases
        with ph.phase("cluster.prep"):
            self._prep_admissions()
        with ph.phase("cluster.evict"):
            completed = self._evict_finished()
        with ph.phase("cluster.admit"):
            self._admit_queued()
        with ph.phase("cluster.gang"):
            completed.update(self._solve_gang())
        if self._pending_completed:
            # level-2 (sliced) completions produced during admission /
            # gang triage — delivered with this round's evictions
            completed.update(self._pending_completed)
            self._pending_completed.clear()
        with ph.phase("cluster.chunk"):
            self._advance_pools()
            if self.step_mode == "sync":
                for pool in self._pools.values():
                    jax.block_until_ready(pool.state.lanes.P)
        self._steps += 1
        self._snapshot_occupancy()
        self._operational_round()
        return completed

    def _on_alert(self, alert) -> None:
        """SLO alert routing (see UOTScheduler._on_alert): note the
        transition in the black box, freeze it when an alert fires."""
        fl = self.obs.flight
        fl.note("alert", slo=alert.name, state=alert.state,
                burn=alert.burn_fast)
        if alert.state == "firing":
            fl.dump(f"alert:{alert.name}", reason=alert.describe())

    def _operational_round(self) -> None:
        """Per-round operational-plane upkeep (null twins under
        obs=False): flight round with the cluster's device-health
        summary, windows tick, SLO evaluation."""
        obs = self.obs
        if obs.flight.enabled:
            obs.flight.record_round(
                self._steps, queued=len(self._queue),
                gang_queued=len(self._gang_queue),
                in_flight=self.in_flight,
                occupancy=self._g_occupancy.value,
                quarantined=self._device_health.count("quarantined"),
                deadline_misses=self._c["deadline_misses"].value)
        if (self._steps % self.op_interval == 0
                or (not self.in_flight and not self.pending)):
            obs.windows.tick()
            obs.slo.evaluate()

    def run(self, max_steps: int | None = None) -> dict[int, np.ndarray]:
        """Step until queues and lanes drain (or ``max_steps`` more steps
        ran); returns all completions."""
        start = self._steps
        out: dict[int, np.ndarray] = {}
        while self.pending or self.in_flight:
            out.update(self.step())
            if max_steps is not None and self._steps - start >= max_steps:
                break
        out.update(self._evict_finished())   # final chunk's completions
        return out

    # ---- internals --------------------------------------------------------

    def _prep_admissions(self) -> None:
        """Decision-free host work for the NEXT admissions: pad each queued
        dense payload to its bucket shape once and cache it. In the async
        loop this runs while the previous chunk is still executing on the
        devices — the 'host admission for chunk t+1 overlaps device chunk
        t' half of the double buffer. Cached payloads are consumed (and
        the cache pruned) at admission; re-padding to a *wider* shared
        pool, when the router goes that way, starts from the cached bucket
        copy."""
        for req in self._queue:
            if req.K is not None and req.rid not in self._prepped:
                Mb, Nb = req.bucket
                M, N = req.shape
                Kp = np.zeros((Mb, Nb), np.float32)
                ap = np.zeros(Mb, np.float32)
                bp = np.zeros(Nb, np.float32)
                Kp[:M, :N] = req.K
                ap[:M] = req.a
                bp[:N] = req.b
                self._prepped[req.rid] = (Kp, ap, bp)

    def _request_kernel(self, req: ScheduledRequest) -> np.ndarray:
        """The request's (M, N) matrix for an off-lane re-solve (dense
        payload or the geometry's Gibbs mirror)."""
        if req.K is not None:
            return req.K
        g = PointCloudGeometry(
            x=jnp.asarray(req.x), y=jnp.asarray(req.y),
            xn=jnp.asarray(req.xn), yn=jnp.asarray(req.yn),
            scale=req.scale)
        return np.asarray(g.kernel(self.cfg.reg))

    def _escalate(self, req: ScheduledRequest):
        """Log-domain retry of a twice-corrupted request (the requeue
        bounce is the FIRST retry — see the module docstring); returns
        ``(P or None, iters)``."""
        if not self.retry_escalate or req.retries >= 2:
            return None, 0
        req.retries += 1
        P, stats, ok = escalate_log_solve(
            self._request_kernel(req), req.a, req.b, self.cfg,
            factor=self.escalate_factor)
        return (P if ok else None), stats["iters"]

    def _requeue(self, req: ScheduledRequest) -> None:
        """Bounce an intact-payload request back through admission: the
        quarantine/poison recovery whose eventual answer is bit-identical
        to the fault-free lane solve (placement invariance). The
        bucket-padded ``_prepped`` cache entry, if any, is still valid."""
        req.retries += 1
        self._c["requeued"].inc()
        self.obs.tracer.emit(req.rid, "requeue", retries=req.retries)
        self.obs.flight.note("requeue", rid=req.rid, retries=req.retries)
        self._queue.append(req)

    def _trim_results(self) -> None:
        while len(self._results) > self.max_results:
            old = next(iter(self._results))
            self._results.pop(old)
            self._c["lost_results"].inc()
            self.obs.tracer.emit(old, "lost")
            self._store_disposition(RequestFailure(
                rid=old, status="lost",
                reason="coupling evicted from the bounded result store "
                       "(max_results) before it was polled"))

    def _scan_device_health(self, flags: dict, completed: dict) -> None:
        """Quarantine devices showing the blackout signature: EVERY active
        lane of the device (>= 2) unhealthy in the same round. A single
        bad lane on an otherwise-fine device is payload/lane poison and is
        handled per-request at eviction; all-lanes-at-once is hardware.
        Quarantined devices are drained (requests bounce back through
        admission) and never receive another placement."""
        active = [0] * self.num_devices
        unhealthy = [0] * self.num_devices
        for bucket, (iters_, conv_, healthy_) in flags.items():
            pool = self._pools[bucket]
            for (d, l) in pool.requests:
                active[d] += 1
                unhealthy[d] += int(not healthy_[d, l])
        for d in range(self.num_devices):
            if (self._device_health[d] == "ok" and active[d] >= 2
                    and unhealthy[d] == active[d]):
                self._device_health[d] = "quarantined"
                self._c["devices_quarantined"].inc()
                fl = self.obs.flight
                if fl.enabled:
                    # dump_on quarantine: the blackout signature is an
                    # incident — capture the rounds that led up to it
                    fl.note("quarantine", device=d, active=active[d])
                    fl.dump("quarantine",
                            reason=f"device {d}: all {active[d]} active "
                                   "lanes unhealthy in one round")
                for bucket in flags:
                    pool = self._pools[bucket]
                    drained = [s for s in pool.requests if s[0] == d]
                    for slot in drained:
                        req = pool.requests.pop(slot)
                        pool.admitted_at.pop(slot)
                        self._c["unhealthy_evictions"].inc()
                        if req.retries == 0:
                            self._requeue(req)
                        else:
                            self._finish_escalated(req, slot,
                                                   pool.bucket,
                                                   completed)
                # no cluster_evict scrub for the drained slots: the whole
                # device slice is already poison and will never be placed
                # to again — scrubbing it would only burn a launch

    def _finish_escalated(self, req: ScheduledRequest, slot, bucket,
                          completed: dict) -> None:
        """Terminal handling for a request past its requeue bounce: one
        log-domain escalation, then a typed failure."""
        d, l = slot
        now = self.clock()
        self.obs.tracer.emit(req.rid, "escalate", retries=req.retries + 1)
        P, n_iters = self._escalate(req)
        if P is not None:
            self._c["retried_ok"].inc()
            completed[req.rid] = self._results[req.rid] = P
            self._trim_results()
            status = "retried_ok"
        else:
            self._c["failed"].inc()
            self._store_disposition(RequestFailure(
                rid=req.rid, status="failed",
                reason="lane state went non-finite twice and the "
                       "log-domain escalation did not recover",
                retries=req.retries))
            status = "failed"
        self._record(ClusterRequestTelemetry(
            rid=req.rid, bucket=bucket, lane=l, arrival=req.arrival,
            admitted=req.arrival, completed=now, iters=n_iters,
            converged=False, deadline=req.deadline, shed=req.shed,
            status=status, retries=req.retries, device=d, route="lane"))

    def _evict_finished(self) -> dict[int, np.ndarray]:
        completed: dict[int, np.ndarray] = {}
        now = self.clock()
        # the first (and in async mode, only) device-blocking read of the
        # in-flight chunk: O(D*L) lifecycle flags per occupied pool
        flags = {
            bucket: (np.asarray(pool.state.lanes.iters),
                     np.asarray(pool.state.lanes.converged),
                     np.asarray(pool.state.lanes.healthy))
            for bucket, pool in self._pools.items() if pool.requests}
        tr = self.obs.tracer
        if tr.enabled:
            # per-request chunk progress (with the serving device), from
            # the host flag copies this pass already fetched — tracing
            # adds zero extra device syncs
            for bucket, (iters_, conv_, healthy_) in flags.items():
                for (d, l), req in self._pools[bucket].requests.items():
                    tr.emit(req.rid, "chunk", lane=l, device=d,
                            iters=int(iters_[d, l]),
                            converged=bool(conv_[d, l]),
                            healthy=bool(healthy_[d, l]))
        # device-level triage first: the blackout signature drains whole
        # devices (requests requeue), so the per-lane loop below only ever
        # sees isolated poison on devices that stay in service
        self._scan_device_health(flags, completed)
        for bucket, (iters, conv, healthy) in flags.items():
            pool = self._pools[bucket]
            finished = [
                slot for slot, req in list(pool.requests.items())
                if not healthy[slot] or conv[slot] or iters[slot] >= (
                    req.max_iters if req.max_iters is not None
                    else self.cfg.num_iters)]
            if not finished:
                continue
            for slot in finished:
                d, l = slot
                req = pool.requests.pop(slot)
                admitted = pool.admitted_at.pop(slot)
                M, N = req.shape
                P = None
                if healthy[slot]:
                    P = np.asarray(stack_get(pool.state.lanes.P, (d, l)))[:M, :N].copy()
                    # host-side double check on the one evicted slice:
                    # poison landing after the convergence latch froze the
                    # lane never crosses the detector's window
                    if not np.all(np.isfinite(P)):
                        P = None
                tr.emit(req.rid, "evict", lane=l, device=d,
                        iters=int(iters[slot]), converged=bool(conv[slot]),
                        healthy=bool(healthy[slot] and P is not None))
                if P is None:
                    self._c["unhealthy_evictions"].inc()
                    if req.retries == 0:
                        # intact host payload -> bounce through admission
                        # to a healthy device; the eviction scatter below
                        # scrubs this lane's NaNs out of the pool
                        self._requeue(req)
                        continue
                    self._finish_escalated(req, slot, pool.bucket,
                                           completed)
                    continue
                timed_out = (self.cfg.tol is not None and not conv[slot]
                             and req.max_iters is None)
                self._c["timed_out"].inc(int(timed_out))
                completed[req.rid] = self._results[req.rid] = P
                self._trim_results()
                n_iters = int(iters[slot])
                rec = ClusterRequestTelemetry(
                    rid=req.rid, bucket=pool.bucket, lane=l,
                    arrival=req.arrival, admitted=admitted,
                    completed=now, iters=n_iters,
                    converged=bool(conv[slot]), deadline=req.deadline,
                    shed=req.shed,
                    status="timed_out" if timed_out else "ok",
                    retries=req.retries, device=d, route="lane",
                    degrade_level=req.degrade_level,
                    est_error=req.est_error,
                    predicted_iters=req.predicted_iters)
                self._record(rec)
                self._device_completed[d] += 1
                if (self.predictive and n_iters > 0
                        and req.max_iters is None):
                    # close the control loop (full lane solves only —
                    # truncated budgets would bias the model): feed the
                    # predictor, refine seconds-per-iteration, audit the
                    # prediction's relative error
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
                            else self._spi_ewma
                            + a_ * (dt - self._spi_ewma))
                    if req.predicted_iters:
                        self._h_pred_err.observe(
                            abs(req.predicted_iters - n_iters) / n_iters)
            # one pool update for the round's evictions across all
            # devices; indices padded with duplicates -> one jit
            # signature — and the zeroing scrubs poisoned lanes' NaNs
            # off devices that remain in service
            pad = (pool.num_devices * pool.lanes_per_device
                   - len(finished))
            slots = finished + [finished[-1]] * pad
            devs = jnp.asarray([s[0] for s in slots], jnp.int32)
            lns = jnp.asarray([s[1] for s in slots], jnp.int32)
            pool.state = cluster_evict(pool.state, devs, lns)
        return completed

    def inject_lane_fault(self, rid: int) -> bool:
        """Chaos/drill hook: NaN the (device, lane) slot currently holding
        ``rid`` (state corruption with an intact host payload — recovers
        via requeue, bit-identical). False when rid is not in a lane."""
        for pool in self._pools.values():
            for (d, l), req in pool.requests.items():
                if req.rid == rid:
                    st = pool.state.lanes
                    pool.state = ClusterLaneState(
                        lanes=dataclasses.replace(
                            st,
                            P=stack_set(st.P, (d, l),
                                        jnp.asarray(jnp.nan, st.P.dtype)),
                            colsum=stack_set(st.colsum, (d, l), jnp.nan),
                            frow=stack_set(st.frow, (d, l), jnp.nan)))
                    return True
        return False

    def inject_device_fault(self, device: int) -> None:
        """Chaos/drill hook: black out one device shard — NaN its entire
        pool-slice state in every pool (``cluster_poison_device``). The
        next eviction round sees every active lane of the device
        unhealthy and quarantines it."""
        self.obs.flight.note("fault", device=device, tag="blackout")
        for pool in self._pools.values():
            pool.state = cluster_poison_device(pool.state, device)

    def _record(self, rec: ClusterRequestTelemetry) -> None:
        """Terminal bookkeeping shared by every SERVED completion path
        (lane eviction, escalation, gang): running counters, latency and
        iteration histograms, and the span's terminal 'complete' event.
        Shed-drops and admission rejections record inline instead — they
        never solved anything and must not skew the served aggregates."""
        if rec.deadline is not None and rec.route != "dropped":
            self._c["deadlined_completed"].inc()
            self._c["deadline_misses"].inc(int(rec.missed))
        self._c["completed"].inc()
        self._h_wait.observe(rec.wait)
        self._h_latency.observe(rec.latency)
        self._h_iters.observe(rec.iters)
        self.obs.tracer.emit(rec.rid, "complete", status=rec.status,
                             iters=rec.iters, retries=rec.retries,
                             device=rec.device, route=rec.route)
        self._log_request(rec)

    def _shed_at_admission(self, req: ScheduledRequest, now: float) -> bool:
        """Same deadline shedding as the single-device scheduler; dropped
        requests get a telemetry-only cluster record."""
        if (self.shed_policy == "none" or req.deadline is None
                or now <= req.deadline):
            return False
        if self.shed_policy == "drop":
            self._c["shed_dropped"].inc()
            self._c["rejected"].inc()
            self._prepped.pop(req.rid, None)
            self._log_request(ClusterRequestTelemetry(
                rid=req.rid, bucket=req.bucket, lane=-1,
                arrival=req.arrival, admitted=now, completed=now,
                iters=0, converged=False, deadline=req.deadline,
                shed="dropped", status="rejected", device=-1,
                route="dropped"))
            self.obs.tracer.emit(req.rid, "shed", policy="drop")
            self.obs.flight.note("shed", rid=req.rid, policy="drop")
            self.obs.tracer.emit(req.rid, "complete", status="rejected",
                                 reason="deadline passed at admission "
                                        "(shed_policy='drop')")
            self._store_disposition(RequestFailure(
                rid=req.rid, status="rejected",
                reason="deadline already passed at admission "
                       "(shed_policy='drop')"))
            return True
        # 'degrade': an expired deadline walks the ladder — level 1
        # normally, deeper when the brownout controller says the whole
        # cluster is already shedding accuracy
        self.obs.tracer.emit(req.rid, "shed", policy="degrade")
        level = max(1, self.brownout.level if self.brownout else 0)
        self._degrade(req, level)
        return False

    def _device_active(self, device: int) -> int:
        return sum(p.device_active(device) for p in self._pools.values())

    def _pool_for(self, req: ScheduledRequest) -> tuple[_ClusterPool, bool]:
        """The pool this request solves in (created on demand); True when
        an existing *wider* pool is shared cross-bucket instead."""
        pool = self._pools.get(req.bucket)
        if pool is not None:
            return pool, False
        if self.share_pools:
            # bucket-affinity cross-bucket sharing: a wider existing pool
            # with a free slot hosts the request via valid-extent masking
            # (zero-padding is exact -> bit-identical results), instead of
            # allocating a new D-device pool stack for a one-off shape
            Mb, Nb = req.bucket
            for bucket in sorted(self._pools):
                cand = self._pools[bucket]
                if (bucket[0] >= Mb and bucket[1] >= Nb
                        and any(cand.free_lanes(d)
                                for d in range(self.num_devices))):
                    self._router["shared_pool"].inc()
                    return cand, True
        pool = self._pools[req.bucket] = _ClusterPool(
            req.bucket, self.num_devices, self.lanes_per_device, self.cfg,
            mesh=self.mesh, axis=self.axis,
            storage_dtype=self.storage_dtype)
        return pool, False

    def _pick_device(self, pool: _ClusterPool) -> int | None:
        """Placement policy: the device shard that takes the next lane."""
        cap = self.device_active_cap
        candidates = [d for d in range(self.num_devices)
                      if self._device_health[d] == "ok"
                      and pool.free_lanes(d)
                      and (cap is None or self._device_active(d) < cap)]
        if not candidates:
            return None
        if self.placement == "bucket_affinity":
            hot = [d for d in candidates if pool.device_active(d) > 0]
            if hot:
                self._router["affinity_hits"].inc()
                # pack: the busiest shard of THIS bucket that still has room
                return max(hot, key=lambda d: (pool.device_active(d), -d))
            self._router["affinity_spills"].inc()
        else:
            self._router["least_loaded"].inc()
        return min(candidates, key=lambda d: (self._device_active(d), d))

    def _admit_queued(self) -> None:
        if not self._queue:
            return
        if (self.gang == "auto"
                and all(h != "ok" for h in self._device_health)):
            # no healthy device shard remains: the gang path still solves
            # per request without touching lane-pool state — degraded
            # capacity, but every request keeps resolving
            self._router["gang_routed"].inc(len(self._queue))
            for req in self._queue:
                self.obs.tracer.emit(req.rid, "queue",
                                     depth=len(self._gang_queue) + 1,
                                     route="gang")
            self._gang_queue.extend(self._queue)
            self._queue = []
            return
        now = self.clock()
        remaining: list[ScheduledRequest] = []
        placements: dict[tuple[int, int], list] = {}   # pool bucket -> slots
        stalled = False
        # predicted-finish-time EDF when the service model is calibrated
        # (least slack = deadline minus predicted service); else plain EDF
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
                continue
            self._degrade_if_infeasible(req, now)
            if brownout_level:
                # sustained overload: new admissions shed accuracy so
                # the backlog drains faster than it grows
                self._degrade(req, brownout_level)
            if req.degrade_level >= 2 and req.K is None:
                # level 2: solve NOW on the host sliced tier — never
                # occupies a (device, lane) slot
                self._prepped.pop(req.rid, None)
                self._complete_sliced(req, now)
                continue
            pool, _shared = self._pool_for(req)
            device = self._pick_device(pool)
            if device is None:
                stalled = True
                remaining.append(req)
                continue
            lane = pool.free_lanes(device)[0]
            pool.requests[(device, lane)] = req
            pool.admitted_at[(device, lane)] = now
            self._device_placed[device] += 1
            self.obs.flight.note("place", rid=req.rid, lane=lane,
                                 device=device)
            self.obs.tracer.emit(req.rid, "place", lane=lane, device=device,
                                 bucket=list(pool.bucket), route="lane")
            placements.setdefault(pool.bucket, []).append(
                (device, lane, req))
        if stalled:
            self._router["placement_stalls"].inc()
        for bucket, placed in placements.items():
            dense = [p for p in placed if p[2].K is not None]
            points: dict[tuple[int, float], list] = {}
            for d, l, r in placed:
                if r.K is None:
                    points.setdefault((r.x.shape[1], r.scale),
                                      []).append((d, l, r))
            if dense:
                self._admit_dense(bucket, dense)
            for (dim, scale), group in points.items():
                self._admit_points(bucket, group, dim, scale)
        self._queue = remaining

    def _admit_dense(self, bucket, placed) -> None:
        pool = self._pools[bucket]
        Mb, Nb = bucket
        # pow2-canonical batch (the bucketed-flush trick), NOT the full
        # D*L capacity: one admission ships one bucket-sized payload, not
        # 64, while jit signatures stay O(log capacity) per payload kind;
        # the index tail is duplicate slots (idempotent scatter)
        cap = ops.canonical_batch(
            len(placed), pool.num_devices * pool.lanes_per_device)
        Kp = np.zeros((cap, Mb, Nb), np.float32)
        ap = np.zeros((cap, Mb), np.float32)
        bp = np.zeros((cap, Nb), np.float32)
        mv = np.zeros(cap, np.int32)
        nv = np.zeros(cap, np.int32)
        devs = np.empty(cap, np.int32)
        lns = np.empty(cap, np.int32)
        for j in range(cap):
            d, l, req = placed[min(j, len(placed) - 1)]
            M, N = req.shape
            prep = self._prepped.pop(req.rid, None)
            if prep is not None and prep[0].shape == (Mb, Nb):
                Kp[j], ap[j], bp[j] = prep
            else:
                # shared wider pool (or unprepped request): pad from the
                # bucket-padded cache if present, else from the raw payload
                src = prep[0] if prep is not None else req.K
                sm, sn = src.shape
                Kp[j, :sm, :sn] = src
                ap[j, :M] = req.a
                bp[j, :N] = req.b
            mv[j], nv[j] = M, N
            devs[j], lns[j] = d, l
        self.obs.traffic.charge_admission(
            route="lane", M=Mb, N=Nb, s=4, source="dense",
            count=len(placed))
        pool.state = cluster_admit(
            pool.state, jnp.asarray(devs), jnp.asarray(lns),
            jnp.asarray(Kp), jnp.asarray(ap), jnp.asarray(bp),
            m_valid=jnp.asarray(mv), n_valid=jnp.asarray(nv))

    def _admit_points(self, bucket, placed, dim: int, scale: float) -> None:
        """Coordinate-payload admission: ship O((M+N)*(d+1)) floats per
        request, materialize the masked Gibbs stack on-device through the
        geometry mirror (bit-identical to dense submission), one pool
        update per (d, scale) group."""
        pool = self._pools[bucket]
        Mb, Nb = bucket
        cap = ops.canonical_batch(
            len(placed), pool.num_devices * pool.lanes_per_device)
        xs = np.zeros((cap, Mb, dim), np.float32)
        xns = np.zeros((cap, Mb), np.float32)
        ys = np.zeros((cap, Nb, dim), np.float32)
        yns = np.zeros((cap, Nb), np.float32)
        mv = np.zeros(cap, np.int32)
        nv = np.zeros(cap, np.int32)
        ap = np.zeros((cap, Mb), np.float32)
        bp = np.zeros((cap, Nb), np.float32)
        devs = np.empty(cap, np.int32)
        lns = np.empty(cap, np.int32)
        for j in range(cap):
            d, l, req = placed[min(j, len(placed) - 1)]
            M, N = req.shape
            xs[j, :M], xns[j, :M] = req.x, req.xn
            ys[j, :N], yns[j, :N] = req.y, req.yn
            mv[j], nv[j] = M, N
            ap[j, :M] = req.a
            bp[j, :N] = req.b
            devs[j], lns[j] = d, l
        g = PointCloudGeometry(
            x=jnp.asarray(xs), y=jnp.asarray(ys), xn=jnp.asarray(xns),
            yn=jnp.asarray(yns), m_valid=jnp.asarray(mv),
            n_valid=jnp.asarray(nv), scale=scale)
        self.obs.traffic.charge_admission(
            route="lane", M=Mb, N=Nb, s=4, source="implicit", d=dim,
            count=len(placed))
        pool.state = cluster_admit(
            pool.state, jnp.asarray(devs), jnp.asarray(lns),
            g.kernel(self.cfg.reg), jnp.asarray(ap), jnp.asarray(bp),
            m_valid=jnp.asarray(mv), n_valid=jnp.asarray(nv))

    def _solve_gang(self) -> dict[int, np.ndarray]:
        """Run up to ``gang_per_step`` over-budget requests on the
        row-sharded gang (the whole mesh per solve). Without a mesh the
        escape hatch degrades to the per-request tier-1 solve — still
        served, still one submit API."""
        if not self._gang_queue:
            return {}
        completed: dict[int, np.ndarray] = {}
        self._gang_queue.sort(key=ScheduledRequest.edf_key)
        budget = self.gang_per_step
        while self._gang_queue and budget > 0:
            req = self._gang_queue.pop(0)
            now = self.clock()
            if req.shed is None and self._shed_at_admission(req, now):
                continue
            if req.degrade_level >= 2 and req.K is None:
                # a point request the shed ladder walked to level 2:
                # the sliced tier is route-independent (host-side, no
                # mesh) and cheaper than any gang launch — take it and
                # keep the gang budget for requests that need the mesh
                self._complete_sliced(req, now)
                continue
            budget -= 1
            t0 = self.clock()
            if req.K is None:
                g = PointCloudGeometry(
                    x=jnp.asarray(req.x), y=jnp.asarray(req.y),
                    xn=jnp.asarray(req.xn), yn=jnp.asarray(req.yn),
                    scale=req.scale)
                K = g.kernel(self.cfg.reg)
            else:
                K = req.K
            # a degraded gang request runs its reduced budget, like a lane
            iters = (self.cfg.num_iters if req.max_iters is None
                     else min(req.max_iters, self.cfg.num_iters))
            if self._gang_degrade:
                # a previous solve breached gang_timeout: keep the gang
                # tier's latency bounded by running the degraded budget
                # (the shed 'degrade' contract applied to the gang)
                iters = min(iters, self.degrade_iters)
            cfg = (self.cfg if iters == self.cfg.num_iters
                   else dataclasses.replace(self.cfg, num_iters=iters))
            if self.mesh is not None:
                P, _ = distributed.gang_solve(
                    self.mesh, self.axis, K, req.a, req.b, cfg,
                    storage_dtype=self.storage_dtype,
                    overlapped=self.gang_overlapped, obs=self.obs)
            else:
                P, _ = ops.solve_fused(
                    jnp.asarray(K), jnp.asarray(req.a), jnp.asarray(req.b),
                    cfg, interpret=self.interpret,
                    storage_dtype=self.storage_dtype)
                P = np.asarray(P)
            done = self.clock()
            status = "ok"
            if (self.gang_timeout is not None
                    and done - t0 > self.gang_timeout):
                # a fused launch can't be preempted: the breaching solve
                # still delivers, is recorded timed_out, and latches the
                # degraded budget for the solves after it
                self._c["gang_timeouts"].inc()
                self._gang_degrade = True
                status = "timed_out"
                self._c["timed_out"].inc()
                fl = self.obs.flight
                if fl.enabled:
                    # dump_on gang_timeout: the latch permanently
                    # degrades the gang tier — incident-worthy
                    fl.note("gang_timeout", rid=req.rid,
                            elapsed=done - t0)
                    fl.dump("gang_timeout",
                            reason=f"rid {req.rid}: gang solve took "
                                   f"{done - t0:.3f}s > "
                                   f"{self.gang_timeout:.3f}s; degraded "
                                   "budget latched")
            completed[req.rid] = self._results[req.rid] = P
            self._trim_results()
            self._c["gang_completed"].inc()
            M, N = req.shape
            gang_devices = (self.num_devices if self.mesh is not None else 1)
            self.obs.tracer.emit(req.rid, "gang", devices=gang_devices,
                                 iters=iters, status=status)
            # gang traffic: the streamed per-request formula on the
            # row-sharded stack + the per-device ring all-reduce bytes
            # (charge_solve adds the collective term for route='gang')
            s = (np.dtype(self.storage_dtype).itemsize
                 if self.storage_dtype is not None else 4)
            self.obs.traffic.charge_solve(
                route="gang", tier="streamed", M=M, N=N, s=s, T=iters,
                source="dense" if req.K is not None else "implicit",
                d=None if req.K is not None else int(req.x.shape[1]))
            self._record(ClusterRequestTelemetry(
                rid=req.rid, bucket=req.bucket, lane=-1,
                arrival=req.arrival, admitted=now, completed=done,
                iters=iters, converged=False, deadline=req.deadline,
                shed=req.shed, status=status, retries=req.retries,
                device=-1, route="gang"))
        return completed

    def _charge_chunk(self, pool: _ClusterPool, counters: dict) -> None:
        """Chunk-advance accounting (see ``UOTScheduler._charge_chunk``):
        one shard_map launch advances the whole device-stacked pool, so
        ``L`` spans every device's lanes."""
        for k, v in counters.items():
            if v:
                self._c_dispatch[k].inc(v)
        if not self.obs.traffic.enabled:
            return
        tier = "resident" if counters["resident"] > 0 else "streamed"
        Mb, Nb = pool.bucket
        self.obs.traffic.charge_chunk(
            route="lane", tier=tier,
            L=pool.num_devices * pool.lanes_per_device, M=Mb, N=Nb,
            s=jnp.dtype(pool.state.lanes.P.dtype).itemsize,
            chunk_iters=self.chunk_iters)

    def _advance_pools(self) -> None:
        # The launch profiler forces a block_until_ready per timed
        # launch; in async mode that sync would destroy the deliberate
        # host/device overlap the double-buffered loop exists for, so
        # kernel profiling is sync-mode only. Phase timers (pure host
        # timestamps) and the dispatch advisor stay on in both modes.
        profiler = (self.obs.profile if self.step_mode == "sync"
                    else None)
        advisor = self._advisor
        for bucket, pool in list(self._pools.items()):
            if pool.requests:
                pool.idle_steps = 0
                with ops.dispatch_counters() as counters, \
                        ops.launch_profiler(profiler), \
                        (ops.dispatch_advisor(advisor)
                         if advisor is not None
                         else contextlib.nullcontext()):
                    pool.state = cluster_stepped(
                        pool.state, self.chunk_iters, self.cfg,
                        mesh=self.mesh, axis=self.axis,
                        interpret=self.interpret, impl=self.impl)
                self._charge_chunk(pool, counters)
            else:
                pool.idle_steps += 1
                if (self.pool_idle_ttl is not None
                        and pool.idle_steps > self.pool_idle_ttl):
                    del self._pools[bucket]

    def _snapshot_occupancy(self) -> None:
        occ = {str(b): p.occupancy for b, p in self._pools.items()}
        self.occupancy_log.append({
            "step": self._steps,
            "queued": len(self._queue),
            "gang_queued": len(self._gang_queue),
            "deadline_misses": self._c["deadline_misses"].value,  # running
            "pools": occ,
            "device_active": [self._device_active(d)
                              for d in range(self.num_devices)],
        })
        self._g_queued.set(len(self._queue))
        self._g_gang_queued.set(len(self._gang_queue))
        self._g_in_flight.set(self.in_flight)
        self._g_occupancy.set(sum(occ.values()) / len(occ) if occ else 0.0)
        # count what falls off the bounded telemetry window so the
        # narrowing of stats()' aggregates is visible, not silent.
        # Request records trim at append time (_log_request — every
        # producer path); the occupancy window's one producer is here.
        self._c["window_dropped_occupancy"].inc(
            max(0, len(self.occupancy_log) - self.max_log))
        del self.occupancy_log[:-self.max_log]

    # ---- telemetry --------------------------------------------------------

    def stats(self) -> dict:
        """Cluster-wide serving telemetry: the single-device aggregate keys
        (over the retained window; running deadline/shed counters exact),
        plus per-device placement/completion/occupancy rollups, router
        decision counts, gang totals, and this scheduler's own
        ``impl='auto'`` dispatch decisions."""
        lanes_cap = self.lanes_per_device
        device_occ = [[] for _ in range(self.num_devices)]
        for snap in self.occupancy_log:
            for d, active in enumerate(snap["device_active"]):
                device_occ[d].append(active / max(1, lanes_cap))
        c = self._c
        cluster = {
            "deadline_misses": c["deadline_misses"].value,
            "miss_rate": (c["deadline_misses"].value
                          / c["deadlined_completed"].value
                          if c["deadlined_completed"].value else 0.0),
            "shed_dropped": c["shed_dropped"].value,
            "shed_degraded": c["shed_degraded"].value,
            "gang_completed": c["gang_completed"].value,
            "router": {k: v.value for k, v in self._router.items()},
            "dispatch": {k: v.value for k, v in self._c_dispatch.items()},
            # fault-containment rollup (running totals, exact — registry
            # counters "cluster.*" in self.obs.registry)
            "rejected": c["rejected"].value,
            "failed": c["failed"].value,
            "retried_ok": c["retried_ok"].value,
            "timed_out": c["timed_out"].value,
            "unhealthy_evictions": c["unhealthy_evictions"].value,
            "lost_results": c["lost_results"].value,
            "requeued": c["requeued"].value,
            "gang_timeouts": c["gang_timeouts"].value,
            "window_dropped": {
                "requests": c["window_dropped_requests"].value,
                "occupancy": c["window_dropped_occupancy"].value,
                "dispositions": c["window_dropped_dispositions"].value,
            },
            # overload-model totals (zeros when the features are off)
            "admission_infeasible": self._c_infeasible.value,
            "degrade_levels": {lvl: ctr.value
                               for lvl, ctr in self._c_degrade.items()},
            "brownout_level": (self.brownout.level
                               if self.brownout is not None else 0),
            "seconds_per_iter": self._seconds_per_iter(),
            "device_health": list(self._device_health),
            "devices": {
                d: {"placed": self._device_placed[d],
                    "completed": self._device_completed[d],
                    "active": self._device_active(d),
                    "health": self._device_health[d],
                    "occupancy_mean": (float(np.mean(device_occ[d]))
                                       if device_occ[d] else 0.0)}
                for d in range(self.num_devices)},
        }
        status_counts: dict[str, int] = {}
        for t in self.request_log:
            status_counts[t.status] = status_counts.get(t.status, 0) + 1
        cluster["status_counts"] = status_counts
        # dropped / admission-rejected requests never solved anything —
        # excluded from the aggregates, which describe served work
        served = [t for t in self.request_log
                  if t.shed != "dropped" and t.status != "rejected"]
        if not served:
            return {"completed": 0, "steps": self._steps, "wait_mean": 0.0,
                    "wait_p99": 0.0, "latency_p50": 0.0, "latency_p99": 0.0,
                    "iters_mean": 0.0, "iters_max": 0,
                    "converged_frac": 0.0, "occupancy_mean": 0.0, **cluster}
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
            **cluster,
        }
