"""One-shot solve driver: one seeded problem, solved back to back
through the configuration's ``entry``, ``solve_fused``:
``repro.kernels.ops.solve_fused(K, a, b, cfg, impl=...)`` on one device.

``solve_s`` is the window's time over the solves completed in it, each
timed from dispatch to its coupling ready on the device. Every solve's
column sums and the last solve's coupling are compared with
``bench.reference.solve`` on the same arrays.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from bench import bytecount, data, reference
from repro.core import UOTConfig
from repro.kernels import ops

WARMUP_SOLVES = 2


def _uot_config(cfg: dict) -> UOTConfig:
    return UOTConfig(reg=cfg["reg"], reg_m=cfg["reg_m"],
                     num_iters=cfg["num_iters"], tol=cfg["tol"],
                     dtype=jnp.dtype(cfg["dtype"]))


def _entry(run, cfg: UOTConfig):
    """The solve function of the configuration's entry."""
    entry = run.config["entry"]
    if entry == "solve_fused":
        impl = run.config["impl"]
        return lambda K, a, b: ops.solve_fused(K, a, b, cfg, impl=impl)
    raise ValueError(f"unknown solve entry {entry!r}")


@jax.jit
def _rel_gap(x, ref):
    """max |x - ref| / max |ref|."""
    return jnp.max(jnp.abs(x - ref)) / jnp.max(jnp.abs(ref))


def run(run) -> None:
    c = run.config
    cfg = _uot_config(c)
    solve = _entry(run, cfg)
    with run.spans.span("bench.data"):
        K, a, b = jax.block_until_ready(
            data.gibbs_2d(run.seed, c["data"], c["reg"]))
    M, N = K.shape
    with run.spans.span("bench.warmup"):
        for _ in range(WARMUP_SOLVES):
            jax.block_until_ready(solve(K, a, b))

    colsums = []
    t0 = run.start_window()
    t = t0
    while t - t0 < run.seconds:
        P_out = colsum = None     # a caller drops the last answer first
        with run.spans.span("bench.solve"):
            P_out, colsum = jax.block_until_ready(solve(K, a, b))
        colsums.append(colsum)
        t = time.perf_counter()
    run.end_window()
    solves = len(colsums)
    run.metrics["solve_s"] = (t - t0) / solves
    run.note(f"solves in the window: {solves}, solve_s "
             f"{(t - t0) / solves!r}")
    run.note_spread("solves", "bench.solve")

    # the reference, after the window and on the same arrays
    with run.spans.span("bench.reference"):
        ref, ref_colsum, iters, drift = reference.solve(
            K, a, b, exponent=reference.fi(c["reg"], c["reg_m"]),
            tol=c["tol"], num_iters=c["num_iters"])
        iters, drift = int(iters), float(drift)
        gaps = [float(_rel_gap(cs, ref_colsum)) for cs in colsums]
        coupling_gap = float(_rel_gap(P_out.astype(jnp.float32), ref))
    limits = run.workload["limits"]
    bad = sum(g > limits["colsum_err"] for g in gaps)
    run.attempted, run.failed = solves, bad
    run.note(f"solves attempted {solves}, completed {solves - bad} within "
             f"the column-sum limit, failed {bad}; reference iterations "
             f"{iters}, last drift {drift!r} (tol {c['tol']})")
    run.check("coupling_err", coupling_gap, limits["coupling_err"])
    run.check("colsum_err", max(gaps), limits["colsum_err"])

    run.facts.update(solves=solves, iters=iters,
                     least_bytes_per_solve=bytecount.least_solve_bytes(
                         M, N, jnp.dtype(c["dtype"]).itemsize, iters))
