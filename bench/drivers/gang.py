"""Gang solve driver: one seeded problem too large for one chip, its rows
split over the cell's devices, solved back to back through the
configuration's ``entry``, ``gang_solve_sharded``:
``repro.core.distributed.gang_solve_sharded(mesh, axis, K, a, b, cfg)``.

The entry is looked up before any data is built, so a program without it
fails at once. K is made on the devices, already sharded by rows, from
the seed's points; no host array of M x N exists.

``solve_s`` is the window's time over the solves completed in it, each
timed from dispatch to its coupling ready on the devices. After the
window K is freed and ``bench.reference_blocked`` solves the same points
on one device; the last solve's coupling is compared with it row block by
row block, on the device that holds each block, every solve's column sums
with its column sums, and every solve's iteration count with its own.
"""
from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import data, mesh as gang_mesh, reference, reference_blocked, trace
from repro.core import UOTConfig, distributed
from repro.obs import get_global

WARMUP_SOLVES = 2
AXIS = "rows"
BLOCK = 1024          # rows of K the reference and the comparison hold


def uot_config(cfg: dict) -> UOTConfig:
    return UOTConfig(reg=cfg["reg"], reg_m=cfg["reg_m"],
                     num_iters=cfg["num_iters"], tol=cfg["tol"],
                     dtype=jnp.dtype(cfg["dtype"]))


def reference_kw(cfg: dict) -> dict:
    return dict(reg=cfg["reg"],
                exponent=reference.fi(cfg["reg"], cfg["reg_m"]),
                tol=cfg["tol"], num_iters=cfg["num_iters"])


@functools.partial(jax.jit, static_argnames=("M", "N", "mass_b"))
def _points(key, *, M: int, N: int, mass_b: float):
    kx, ky, ka, kb = jax.random.split(jax.random.key(data.BASE_SEED), 4)
    kr, kc = jax.random.split(key)
    rows = jax.random.permutation(kr, M)
    cols = jax.random.permutation(kc, N)
    x = jax.random.uniform(kx, (M, 2))[rows]
    y = jax.random.uniform(ky, (N, 2))[cols]
    a = jax.random.uniform(ka, (M,), minval=0.5, maxval=1.5)[rows]
    b = jax.random.uniform(kb, (N,), minval=0.5, maxval=1.5)[cols]
    return x, y, a / a.sum(), b / b.sum() * mass_b


def points(seed: int, d: dict):
    """``(x, y, a, b)`` of the cell's problem: uniform points of the unit
    square and marginals of mass 1 and ``mass_b``, drawn once from
    ``bench.data.BASE_SEED``; ``seed`` permutes the rows and the columns,
    so every seed asks for the same iterations."""
    return _points(data.key_from_seed(seed), M=d["M"], N=d["N"],
                   mass_b=d["mass_b"])


def problem(mesh: Mesh, x, y, a, b, reg: float):
    """K (sharded by rows), a (sharded) and b (replicated) on ``mesh``."""
    rows, rep = NamedSharding(mesh, P(AXIS, None)), NamedSharding(mesh, P())
    gibbs = jax.jit(functools.partial(reference_blocked.gibbs, reg=reg),
                    out_shardings=rows)
    K = gibbs(jax.device_put(x, NamedSharding(mesh, P(AXIS))),
              jax.device_put(y, rep))
    return (K, jax.device_put(a, NamedSharding(mesh, P(AXIS))),
            jax.device_put(b, rep))


@functools.lru_cache(maxsize=None)
def _gap_fn(mesh: Mesh, reg: float, block: int):
    def local(A, x, u, y, v):
        def step(carry, i):
            gap, top = carry
            ref = reference_blocked.coupling(
                jax.lax.dynamic_slice_in_dim(x, i * block, block), y,
                jax.lax.dynamic_slice_in_dim(u, i * block, block), v,
                reg=reg)
            Ai = jax.lax.dynamic_slice_in_dim(A, i * block, block)
            return (jnp.maximum(gap, jnp.max(jnp.abs(Ai - ref))),
                    jnp.maximum(top, jnp.max(jnp.abs(ref)))), None
        (gap, top), _ = jax.lax.scan(step, (jnp.float32(0), jnp.float32(0)),
                                     jnp.arange(A.shape[0] // block))
        return gap[None], top[None]
    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(AXIS, None), P(AXIS), P(AXIS), P(), P()),
        out_specs=(P(AXIS), P(AXIS)), check_vma=False))


def coupling_gap(mesh: Mesh, A, x, y, u, v, reg: float) -> float:
    """max |A - diag(u) K diag(v)| / max |diag(u) K diag(v)| over the whole
    coupling, each device comparing the rows it holds, ``BLOCK`` at a
    time (fewer where its rows do not divide)."""
    block = math.gcd(A.shape[0] // mesh.size, BLOCK)
    put = jax.device_put
    rows, rep = NamedSharding(mesh, P(AXIS)), NamedSharding(mesh, P())
    gap, top = _gap_fn(mesh, reg, block)(
        A, put(x, rows), put(u, rows), put(y, rep), put(v, rep))
    return float(np.max(np.asarray(gap)) / np.max(np.asarray(top)))


@jax.jit
def _rel_gap(x, ref):
    """max |x - ref| / max |ref|."""
    return jnp.max(jnp.abs(x - ref)) / jnp.max(jnp.abs(ref))


def run(run) -> None:
    c = run.config
    entry = getattr(distributed, c["entry"])
    cfg = uot_config(c)
    devices = run.used_devices
    mesh = Mesh(np.array(devices), (AXIS,))
    solve = functools.partial(entry, mesh, AXIS, cfg=cfg)
    with run.spans.span("bench.data"):
        x, y, a_m, b_m = jax.block_until_ready(points(run.seed, c["data"]))
        K, a, b = jax.block_until_ready(problem(mesh, x, y, a_m, b_m,
                                                c["reg"]))
    M, N = K.shape
    with run.spans.span("bench.warmup"):
        for _ in range(WARMUP_SOLVES):
            jax.block_until_ready(solve(K, a, b))

    colsums, counts = [], []
    t0 = run.start_window()
    t = t0
    while t - t0 < run.seconds:
        A_out = colsum = None     # a caller drops the last answer first
        with run.spans.span("bench.solve"):
            A_out, colsum, iters = solve(K, a, b)
            jax.block_until_ready(A_out)
        colsums.append(colsum)
        counts.append(int(iters))
        t = time.perf_counter()
    run.end_window()
    solves = len(colsums)
    run.metrics["solve_s"] = (t - t0) / solves
    run.note(f"solves in the window: {solves}, solve_s "
             f"{(t - t0) / solves!r}, iterations {sorted(set(counts))}")
    run.note_spread("solves", "bench.solve")
    counter = get_global().registry.counter
    run.note(f"program counters since start: gang.iters "
             f"{counter('gang.iters').value}, gang.allreduce_bytes "
             f"{counter('gang.allreduce_bytes').value}")
    if run.trace:
        pct = gang_mesh.exposed_collective_pct(gang_mesh.load(
            trace.find_xplane(run.trace_dir)))
        if pct is not None:
            run.facts["collective_exposed_pct"] = pct

    # the reference, after the window and with K freed
    del K
    with run.spans.span("bench.reference"):
        home = devices[0]
        u, v, ref_colsum, ref_iters, drift = reference_blocked.solve(
            *(jax.device_put(z, home) for z in (x, y, a_m, b_m)),
            block=math.gcd(M, BLOCK), **reference_kw(c))
        ref_iters, drift = int(ref_iters), float(drift)
        coupling_err = coupling_gap(mesh, A_out, x, y, u, v, c["reg"])
        ref_colsum = jax.device_put(ref_colsum, colsums[0].sharding)
        gaps = [float(_rel_gap(cs, ref_colsum)) for cs in colsums]
    limits = run.workload["limits"]
    bad = sum(g > limits["colsum_err"] or n != ref_iters
              for g, n in zip(gaps, counts))
    run.attempted, run.failed = solves, bad
    run.note(f"solves attempted {solves}, completed {solves - bad} within "
             f"the limits, failed {bad}; reference iterations {ref_iters}, "
             f"last drift {drift!r} (tol {c['tol']})")
    run.check("coupling_err", coupling_err, limits["coupling_err"])
    run.check("colsum_err", max(gaps), limits["colsum_err"])
    run.check("iters_off", max(abs(n - ref_iters) for n in counts),
              limits["iters_off"])

    run.facts.update(
        solves=solves, iters=ref_iters,
        least_bytes_per_solve=gang_mesh.least_bytes_per_device(
            M, N, len(devices), jnp.dtype(c["dtype"]).itemsize, ref_iters))
