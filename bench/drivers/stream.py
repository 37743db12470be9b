"""Request-stream driver: seeded requests through one
``repro.serve.UOTScheduler``, as a closed loop.

Set-up builds every request's arrays, then warms the scheduler up with
``lanes_per_pool`` requests of each ``(M, N)`` of the mix, so that every
pool, every kind of admission and every lane's read-back has been built
before the window. The same scheduler then serves the window.

``clients`` clients each send their next request when the last one
came back. ``completed_rps`` counts the requests returned within the
window and within the limits, over the window's length. After the window
the loop drains, for at most the workload's ``drain_s``.

Every coupling returned is compared with ``bench.reference`` on the same
arrays once the window has closed.
"""
from __future__ import annotations

import collections
import time

import jax.numpy as jnp
import numpy as np

from bench import data, generator, reference
from repro.core import UOTConfig
from repro.serve import UOTScheduler
from repro.serve.scheduler import QueueFullError
from repro.core.health import InvalidProblemError

REF_BATCH = 16          # problems per reference call (one compile a shape)
WARMUP_WAVES = 4
WARMUP_BASE = 10 ** 6   # warm-up problems lie apart from the window's


def _scheduler(run) -> UOTScheduler:
    c = run.config
    cfg = UOTConfig(reg=c["reg"], reg_m=c["reg_m"], num_iters=c["num_iters"],
                    tol=c["tol"], dtype=jnp.dtype(c["dtype"]))
    return UOTScheduler(cfg, **c["scheduler"])


def _submit(sched, spec, arr, deadline):
    if spec.kind == "dense":
        return sched.submit(arr["K"], arr["a"], arr["b"], deadline=deadline)
    return sched.submit_points(arr["x"], arr["y"], arr["a"], arr["b"],
                               scale=float(spec.d), deadline=deadline)


def _warmup_specs(mix: dict, lanes: int) -> list:
    kinds = [(k["kind"], k.get("d")) for k in mix["kinds"]
             for _ in range(k["count"])]
    shapes = dict.fromkeys((s.M, s.N) for s in generator.composition(mix))
    specs = []
    for M, N in shapes:
        for j in range(lanes):
            kind, d = kinds[j % len(kinds)]
            specs.append(generator.RequestSpec(
                kind, M, N, d, base=WARMUP_BASE + len(specs)))
    return specs


def _warmup(run, sched, limit_s: float) -> int:
    """Serve the warm-up requests; returns how many never came back."""
    c = run.config
    rng = data.rng_from_seed(run.seed, 3)
    specs = _warmup_specs(run.traffic, c["scheduler"]["lanes_per_pool"])
    for spec in specs:
        _submit(sched, spec, data.request_arrays(rng, spec, c["data"],
                                                 c["reg"]),
                sched.clock() + limit_s)
    # every warm-up request fits a pool within a few waves of a full solve
    done = sched.run(max_steps=WARMUP_WAVES * (
        -(-c["num_iters"] // c["scheduler"]["chunk_iters"]) + 2))
    return len(specs) - len(done)


class Ledger:
    """What happened to each request of the window, by its index."""

    def __init__(self):
        self.rid_to_index: dict[int, int] = {}
        self.sent: dict[int, float] = {}      # index -> seconds into window
        self.done: dict[int, float] = {}
        self.results: dict[int, np.ndarray] = {}
        self.refused: set[int] = set()
        self.by_index: dict[int, int] = {}    # index -> distinct request

    def send(self, run, sched, i, spec, arr, t, deadline, distinct=None):
        self.sent[i] = t
        self.by_index[i] = i if distinct is None else distinct
        try:
            with run.spans.span("serve.submit"):
                rid = _submit(sched, spec, arr, deadline)
            self.rid_to_index[rid] = i
        except (QueueFullError, InvalidProblemError):
            self.refused.add(i)

    def receive(self, out: dict, t: float) -> list[int]:
        got = []
        for rid, P in out.items():
            if rid not in self.rid_to_index:
                continue          # a warm-up request that never finished
            i = self.rid_to_index[rid]
            self.done[i], self.results[i] = t, P
            got.append(i)
        return got

    def statuses(self, sched) -> dict[int, str]:
        return {self.rid_to_index[r.rid]: r.status for r in sched.request_log
                if r.rid in self.rid_to_index}


def _step(run, sched, ledger, t0) -> list[int]:
    with run.spans.span("serve.step"):
        out = sched.step()
    return ledger.receive(out, time.perf_counter() - t0)


def _closed_loop(run, sched, specs, arrays, limit_s) -> Ledger:
    ledger = Ledger()
    clients = run.traffic["clients"]
    nxt = collections.Counter()           # client -> requests sent
    owner: dict[int, int] = {}            # index -> client
    count = 0

    def send(client, t):
        nonlocal count
        j = (client + nxt[client] * clients) % len(specs)
        nxt[client] += 1
        owner[count] = client
        ledger.send(run, sched, count, specs[j], arrays[j], t,
                    sched.clock() + limit_s, distinct=j)
        count += 1

    t0 = run.start_window()
    for client in range(clients):
        send(client, 0.0)
    while True:
        now = time.perf_counter() - t0
        if not (sched.pending or sched.in_flight) or now > (
                run.seconds + run.workload["drain_s"]):
            break
        for i in _step(run, sched, ledger, t0):
            if ledger.done[i] < run.seconds:
                send(owner[i], ledger.done[i])
    run.end_window()
    return ledger


def _reference(run, specs, arrays, indices) -> dict[int, np.ndarray]:
    """Reference couplings of the distinct requests ``indices``, batched
    by shape on the device, returned on the host."""
    c = run.config
    kw = dict(exponent=reference.fi(c["reg"], c["reg_m"]), tol=c["tol"],
              num_iters=c["num_iters"])
    groups = collections.defaultdict(list)
    for j in indices:
        groups[specs[j].shape_key].append(j)
    out = {}
    for (kind, d, _, _), js in groups.items():
        for s in range(0, len(js), REF_BATCH):
            part = js[s:s + REF_BATCH]
            pad = part + [part[-1]] * (REF_BATCH - len(part))
            arr = [arrays[j] for j in pad]
            stack = {k: np.stack([x[k] for x in arr]) for k in arr[0]}
            if kind == "dense":
                P = reference.solve_dense_batch(
                    stack["K"], stack["a"], stack["b"], **kw)[0]
            else:
                P = reference.solve_points_batch(
                    stack["x"], stack["y"], stack["a"], stack["b"],
                    scale=float(d), reg=c["reg"], **kw)[0]
            P = np.asarray(P)
            out.update({j: P[k] for k, j in enumerate(part)})
    return out


def run(run) -> None:
    c = run.config
    limit_s = c["latency_limit_s"]
    sched = _scheduler(run)
    specs = generator.requests(run.traffic, run.seed, run.seconds)
    with run.spans.span("bench.data"):
        rng = data.rng_from_seed(run.seed, 2)
        arrays = [data.request_arrays(rng, s, c["data"], c["reg"])
                  for s in specs]
    with run.spans.span("bench.warmup"):
        warmup_unresolved = _warmup(run, sched, limit_s)

    ledger = _closed_loop(run, sched, specs, arrays, limit_s)
    status = ledger.statuses(sched)
    steps, step_s = run.spans.total("serve.step")
    run.facts.update(steps=steps, step_s=step_s)
    run.note_spread("scheduler rounds", "serve.step")
    del sched

    with run.spans.span("bench.reference"):
        refs = _reference(run, specs, arrays,
                          sorted(set(ledger.by_index.values())))
    limits = run.workload["limits"]
    gaps = {}
    for i, P in ledger.results.items():
        R = refs[ledger.by_index[i]]
        gaps[i] = float(np.max(np.abs(P.astype(np.float32) - R))
                        / np.max(np.abs(R)))
    attempted = len(ledger.sent)
    ok = {i for i, g in gaps.items()
          if g <= limits["coupling_err"] and status.get(i) == "ok"}
    unresolved = attempted - len(ledger.results)
    run.attempted, run.failed = attempted, attempted - len(ok)
    counts = collections.Counter(status.values())
    run.note(f"requests attempted {attempted}, completed "
             f"{len(ledger.results)}, failed {attempted - len(ok)} "
             f"(refused {len(ledger.refused)}, statuses {dict(counts)})")
    run.check("coupling_err", max(gaps.values(), default=float("inf")),
              limits["coupling_err"])
    run.check("unresolved", unresolved, 0)
    run.check("warmup_unresolved", warmup_unresolved, 0)

    done = sum(1 for i in ok if ledger.done[i] <= run.seconds)
    run.metrics["completed_rps"] = done / run.seconds
    run.note(f"completed within the window and the limits: {done} "
             f"requests, {done / run.seconds!r} per second")
