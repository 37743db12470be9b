#!/usr/bin/env python3
"""Smoke run of the MAP-UOT solve and serving path on a TPU.

    python3 chip_smoke.py [--seed S]      # one chip: solve, resident, serve
    python3 chip_smoke.py --chips 4       # four chips: cluster, gang only

One process drives every phase through the entry points a user calls
(``repro.kernels.ops`` solve entries, ``repro.serve.UOTScheduler``,
``repro.cluster.ClusterScheduler``, ``core.distributed.gang_solve``) and
compares every answer with the plain fp32 reference
``core.sinkhorn_uot_baseline`` run on the same chip. Data comes from
``--seed``. Each phase prints one line; the last line of a run that passed
is ``{"ok": true, "device": {...}}``.

The run exits nonzero, and prints no ``"ok"`` line, when JAX finds no TPU,
when a phase raises or misses its tolerance, or when the compiled program
of a kernel phase holds no ``tpu_custom_call`` (a kernel that quietly ran
as a reference or in interpret mode). JAX's persistent compilation cache
goes to ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else to
``.jax_cache`` next to this file.

Tolerances, as max |P - ref| / max |ref| ("err") and relative total mass:
  FP32_BAR  3e-5: the fp32 parity bar of the assembled-solver tests
            (tests/test_kernels.py); the kernels and the reference sum in
            different orders, nothing else differs.
  BF16_BARS 5e-2 pointwise, 1e-2 mass: tests/test_bf16_accumulation.py's
            bars for bf16 storage with fp32 accumulation.
  STOP_BAR  10 * tol: phases with ``cfg.tol`` stop on the reference's own
            rule, but where the drift crosses tol within rounding the two
            can stop one iteration apart, and one iteration near the stop
            moves entries by O(tol) relative.
"""
from __future__ import annotations

import argparse
import collections
import functools
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.cluster import ClusterScheduler, cluster_mesh  # noqa: E402
from repro.core import UOTConfig, sinkhorn_uot_baseline  # noqa: E402
from repro.core.distributed import gang_solve, shard_inputs  # noqa: E402
from repro.geometry import PointCloudGeometry  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.serve import UOTScheduler  # noqa: E402

FP32_BAR = 3e-5
BF16_POINT_BAR, BF16_MASS_BAR = 5e-2, 1e-2

# Sizes. SOLVE_N is the paper's largest problem (about 1.7 GB per fp32
# M x N array); the rest are what a UOT service's requests look like.
SOLVE_N = 20480
SOLVE_ITERS = 50
REG, REG_M = 0.05, 1.0
RES_STACK = (32, 256)          # 32 problems of 256 x 256
RES_SINGLE = 1024              # one 1024 x 1024 problem
RES_PC = (1024, 2048, 3)       # point clouds M x N at d = 3
TOL = 1e-4
STOP_BAR = 10 * TOL
RES_ITERS = 1000
SERVE_REQUESTS = 64
SERVE_SIDES = (64, 256, 512, 1024)
SERVE_ITERS = 300
LANES, CHUNK = 8, 10


def require_kernel(compiled, what: str) -> None:
    """Fail when a compiled kernel phase holds no Pallas TPU kernel."""
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{what}: compiled program has no "
                             f"tpu_custom_call (no Pallas kernel ran)")


def _check(what: str, errs: dict, bars: dict) -> None:
    for k, bar in bars.items():
        if not errs[k] <= bar:
            raise AssertionError(f"{what}: {k}={errs[k]:.3e} > {bar:.1e}")


def _fmt(errs: dict) -> str:
    return " ".join(f"{k}={v:.3e}" for k, v in errs.items())


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the four-chip cluster and gang paths")
    args = p.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX platform is "
                         f"{devices[0].platform!r})")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, found {len(devices)}")
    print(f"compile cache: {compile_cache.enable(ROOT)}", flush=True)

    t0 = time.perf_counter()
    if args.chips == 1:
        phase_solve(args.seed)
        phase_resident(args.seed)
        phase_serve(args.seed)
    else:
        phase_cluster(args.seed)
        phase_gang(args.seed)
    print(f"wall_s={time.perf_counter() - t0:.1f} (all phases, compiles "
          f"included)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


# ---- data and reference -----------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2))
def _dense(key, M, N, mass_b=1.2):
    """Gibbs kernel of a squared-distance cost between uniform 2-D points
    (cost in [0, 1]) and positive marginals of unequal mass."""
    kx, ky, ka, kb = jax.random.split(key, 4)
    x = jax.random.uniform(kx, (M, 2))
    y = jax.random.uniform(ky, (N, 2))
    C = ((x[:, None, 0] - y[None, :, 0]) ** 2
         + (x[:, None, 1] - y[None, :, 1]) ** 2) / 2.0
    a = jax.random.uniform(ka, (M,), minval=0.5, maxval=1.5)
    b = jax.random.uniform(kb, (N,), minval=0.5, maxval=1.5)
    return jnp.exp(-C / REG), a / a.sum(), b / b.sum() * mass_b


@jax.jit
def _error_terms(P, ref):
    P = P.astype(jnp.float32)
    return (jnp.max(jnp.abs(P - ref)) / jnp.max(jnp.abs(ref)),
            jnp.abs(P.sum() / ref.sum() - 1.0))


def _errors(P, ref) -> dict:
    """err = max |P - ref| / max |ref|, mass = |sum P / sum ref - 1|;
    computed on the device, returned as floats."""
    err, mass = _error_terms(P, ref)
    return {"err": float(err), "mass": float(mass)}


def _reference(K, a, b, cfg):
    return sinkhorn_uot_baseline(K, a, b, cfg)[0]


def _compile_and_run(fn, *args, what: str):
    """Lower and compile ``fn`` once, require a kernel in it, run it twice.
    Returns (outputs, compile seconds, steady-state seconds)."""
    t = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t
    require_kernel(compiled, what)
    jax.block_until_ready(compiled(*args))
    t = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, compile_s, time.perf_counter() - t


# ---- phases -----------------------------------------------------------------

def phase_solve(seed: int) -> None:
    """The paper's largest dense problem through ``ops.solve_fused``."""
    cfg = UOTConfig(reg=REG, reg_m=REG_M, num_iters=SOLVE_ITERS)
    K, a, b = _dense(jax.random.key(seed), SOLVE_N, SOLVE_N)
    t = time.perf_counter()
    ref = jax.block_until_ready(_reference(K, a, b, cfg))
    print(f"solve: M=N={SOLVE_N} reference sinkhorn_uot_baseline "
          f"{SOLVE_ITERS} iters, first-call s={time.perf_counter() - t:.2f} "
          f"(compile included)", flush=True)
    for sdt in (jnp.float32, jnp.bfloat16):
        for impl in ("auto", None):
            what = (f"solve {jnp.dtype(sdt).name} "
                    f"impl={impl or 'default'}")
            with ops.dispatch_counters() as tiers:
                (P, _), compile_s, steady_s = _compile_and_run(
                    lambda K, a, b: ops.solve_fused(
                        K, a, b, cfg, impl=impl, storage_dtype=sdt),
                    K, a, b, what=what)
            errs = _errors(P, ref)
            del P
            if sdt == jnp.float32:
                _check(what, errs, {"err": FP32_BAR})
            else:
                _check(what, errs, {"err": BF16_POINT_BAR,
                                    "mass": BF16_MASS_BAR})
            tier = ("resident" if tiers["resident"] else "streamed")
            print(f"{what}: M=N={SOLVE_N} tier={tier} {_fmt(errs)} "
                  f"compile_s={compile_s:.2f} steady_s={steady_s:.4f} "
                  f"(one {SOLVE_ITERS}-iteration solve, second call)",
                  flush=True)


def phase_resident(seed: int) -> None:
    """``impl='auto'`` routing to the VMEM-resident kernels, with tol."""
    cfg = UOTConfig(reg=REG, reg_m=REG_M, num_iters=RES_ITERS, tol=TOL)
    key = jax.random.key(seed + 1)
    B, n = RES_STACK
    stack = jax.vmap(lambda k: _dense(k, n, n))(jax.random.split(key, B))
    single = _dense(jax.random.fold_in(key, 1), RES_SINGLE, RES_SINGLE)
    cases = [
        (f"resident stack {B}x{n}x{n}", stack, None),
        (f"resident single {RES_SINGLE}x{RES_SINGLE}",
         tuple(x[None] for x in single), None),
    ]
    M, N, d = RES_PC
    kx, ky, ka, kb = jax.random.split(jax.random.fold_in(key, 2), 4)
    g = PointCloudGeometry.from_points(
        jax.random.uniform(kx, (M, d)), jax.random.uniform(ky, (N, d)),
        scale=float(d))
    a = jax.random.uniform(ka, (M,), minval=0.5, maxval=1.5)
    b = jax.random.uniform(kb, (N,), minval=0.5, maxval=1.5)
    a, b = a / a.sum(), b / b.sum() * 1.2
    cases.append((f"resident points {M}x{N} d={d}",
                  (g.kernel(REG)[None], a[None], b[None]), g))

    batched_ref = jax.jit(jax.vmap(
        lambda K, a, b: sinkhorn_uot_baseline(K, a, b, cfg)))
    for what, (K, a, b), geom in cases:
        with ops.dispatch_counters() as tiers:
            if geom is None:
                fn = (lambda K, a, b: ops.solve_fused_batched(
                    K, a, b, cfg, impl="auto"))
                args = (K, a, b)
            else:
                fn = (lambda a, b: ops.solve_fused(
                    None, a, b, cfg, geometry=geom, impl="auto"))
                args = (a[0], b[0])
            (P, _), compile_s, steady_s = _compile_and_run(
                fn, *args, what=what)
        if tiers != {"resident": 1, "streamed": 0}:
            raise AssertionError(f"{what}: impl='auto' chose {tiers}, "
                                 f"not the resident tier")
        ref, stats = batched_ref(K, a, b)
        errs = _errors(P.reshape(ref.shape), ref)
        _check(what, errs, {"err": STOP_BAR})
        print(f"{what}: tier=resident {_fmt(errs)} ref_iters="
              f"{int(stats['iters'].min())}-{int(stats['iters'].max())} "
              f"compile_s={compile_s:.2f} steady_s={steady_s:.4f} "
              f"(one solve, second call)", flush=True)


def _requests(seed: int):
    """SERVE_REQUESTS seeded requests: half dense, a quarter point clouds
    at d=3 and a quarter at d=32, sides drawn from SERVE_SIDES."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(SERVE_REQUESTS):
        M, N = (int(s) for s in rng.choice(SERVE_SIDES, 2))
        a = rng.uniform(0.5, 1.5, M).astype(np.float32)
        b = rng.uniform(0.5, 1.5, N).astype(np.float32)
        a, b = a / a.sum(), b / b.sum() * 1.1
        d = (None, None, 3, 32)[i % 4]
        if d is None:
            C = rng.uniform(0, 1, (M, N)).astype(np.float32)
            out.append(("dense", np.exp(-C / REG), a, b))
        else:
            x = rng.uniform(0, 1, (M, d)).astype(np.float32)
            y = rng.uniform(0, 1, (N, d)).astype(np.float32)
            out.append(("points", (x, y, float(d)), a, b))
    return out


def _submit_all(sched, reqs, deadline_s: float = 600.0):
    now = sched.clock()
    rids = []
    for kind, payload, a, b in reqs:
        if kind == "dense":
            rids.append(sched.submit(payload, a, b,
                                     deadline=now + deadline_s))
        else:
            x, y, scale = payload
            rids.append(sched.submit_points(x, y, a, b, scale=scale,
                                            deadline=now + deadline_s))
    return rids


def _request_kernel(kind, payload):
    if kind == "dense":
        return jnp.asarray(payload)
    x, y, scale = payload
    return PointCloudGeometry.from_points(x, y, scale=scale).kernel(REG)


def _serve_cfg():
    return UOTConfig(reg=REG, reg_m=REG_M, num_iters=SERVE_ITERS, tol=TOL)


def _run_scheduler(sched, reqs):
    rids = _submit_all(sched, reqs)
    t = time.perf_counter()
    results = sched.run()
    wall = time.perf_counter() - t
    missing = [r for r in rids if r not in results]
    if missing:
        raise AssertionError(f"{len(missing)} requests did not resolve to "
                             f"a coupling: rids {missing[:8]}")
    return rids, results, wall


def phase_serve(seed: int) -> None:
    """About 64 seeded requests through one ``UOTScheduler``."""
    cfg = _serve_cfg()
    reqs = _requests(seed + 2)
    sched = UOTScheduler(cfg, lanes_per_pool=LANES, chunk_iters=CHUNK,
                         impl="auto")
    rids, results, wall = _run_scheduler(sched, reqs)
    worst = {"err": 0.0, "mass": 0.0}
    for rid, (kind, payload, a, b) in zip(rids, reqs):
        ref = _reference(_request_kernel(kind, payload), jnp.asarray(a),
                         jnp.asarray(b), cfg)
        errs = _errors(jnp.asarray(results[rid]), ref)
        _check(f"serve rid {rid} ({kind})", errs, {"err": STOP_BAR})
        worst = {k: max(worst[k], v) for k, v in errs.items()}
    # the chunk advance of the largest pool, compiled as the scheduler
    # runs it, must be a Pallas kernel
    state = ops.make_lane_state(LANES, max(SERVE_SIDES), max(SERVE_SIDES),
                                cfg)
    require_kernel(jax.jit(lambda st: ops.solve_fused_stepped(
        st, CHUNK, cfg, impl="auto")).lower(state).compile(),
        "serve chunk advance")
    st = sched.stats()
    escalated = st["retried_ok"] + st["failed"]
    reg = sched.obs.registry
    tiers = {k: reg.counter(f"serve.dispatch.{k}").value
             for k in ("resident", "streamed")}
    print(f"serve: served={len(results)} refused={st['rejected']} "
          f"escalated={escalated} timed_out={st['timed_out']} "
          f"chunks={tiers} worst {_fmt(worst)} (bar err {STOP_BAR:.0e}) "
          f"run_wall_s={wall:.2f} (compiles included)", flush=True)


def phase_cluster(seed: int) -> None:
    """The serve request set on ``ClusterScheduler`` over a 4-device mesh,
    per request against a one-device ``UOTScheduler`` in this process."""
    cfg = _serve_cfg()
    reqs = _requests(seed + 2)
    one = UOTScheduler(cfg, lanes_per_pool=LANES, chunk_iters=CHUNK,
                       impl="auto")
    rids1, res1, wall1 = _run_scheduler(one, reqs)
    mesh = cluster_mesh(4)
    sched = ClusterScheduler(cfg, mesh=mesh, lanes_per_device=LANES,
                             chunk_iters=CHUNK, impl="auto")
    rids4, res4, wall4 = _run_scheduler(sched, reqs)
    identical, total, worst = collections.Counter(), collections.Counter(), 0.0
    for r1, r4, (kind, *_) in zip(rids1, rids4, reqs):
        P1, P4 = res1[r1], res4[r4]
        identical[kind] += int(np.array_equal(P1, P4))
        total[kind] += 1
        worst = max(worst, float(np.abs(P4 - P1).max() / np.abs(P1).max()))
    if worst > FP32_BAR:
        raise AssertionError(f"cluster vs one device: err={worst:.3e} > "
                             f"{FP32_BAR:.0e}")
    for pool in sched._pools.values():
        shards = sorted((s.device.id, s.index[0].start)
                        for s in pool.state.lanes.P.addressable_shards)
        print(f"cluster pool {pool.bucket}: P shards (device, first row "
              f"of the device axis) {shards}", flush=True)
    done = collections.Counter(rec.device for rec in sched.request_log
                               if rec.route == "lane")
    per_device = [done.get(d, 0) for d in range(4)]
    if min(per_device) == 0:
        raise AssertionError(f"completions per device {per_device}: some "
                             f"device completed nothing")
    print(f"cluster: served={len(res4)} per_device={per_device} "
          f"bit_identical dense={identical['dense']}/{total['dense']} "
          f"points={identical['points']}/{total['points']} err={worst:.3e} "
          f"(bar {FP32_BAR:.0e}) run_wall_s one_device={wall1:.2f} "
          f"mesh4={wall4:.2f} (compiles included)", flush=True)


def phase_gang(seed: int) -> None:
    """One 20480^2 fp32 solve on the row-sharded gang over four chips."""
    cfg = UOTConfig(reg=REG, reg_m=REG_M, num_iters=SOLVE_ITERS)
    K, a, b = _dense(jax.random.key(seed), SOLVE_N, SOLVE_N)
    ref = np.asarray(_reference(K, a, b, cfg))
    mesh = cluster_mesh(4)
    sA, _, _ = shard_inputs(mesh, "devices", K, a, b)
    shards = sorted((s.device.id, s.index[0].start)
                    for s in sA.addressable_shards)
    del sA
    print(f"gang: A shards (device, first row) {shards}", flush=True)
    t = time.perf_counter()
    P, _ = gang_solve(mesh, "devices", K, a, b, cfg)
    wall = time.perf_counter() - t
    err = float(np.abs(P - ref).max() / np.abs(ref).max())
    mass = float(abs(P.sum(dtype=np.float64) / ref.sum(dtype=np.float64)
                     - 1.0))
    if len({d for d, _ in shards}) != 4:
        raise AssertionError(f"gang shards on devices {shards}")
    _check("gang", {"err": err}, {"err": FP32_BAR})
    print(f"gang: M=N={SOLVE_N} {SOLVE_ITERS} iters on 4 devices "
          f"err={err:.3e} mass={mass:.3e} (bar err {FP32_BAR:.0e}) "
          f"first_call_s={wall:.2f} (compile and host transfers included)",
          flush=True)


if __name__ == "__main__":
    main()
