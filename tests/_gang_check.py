"""Subprocess body of tests/test_gang.py: the row-sharded gang on four
forced host devices (XLA's device count is fixed when JAX starts, so the
flag is set here, in a fresh interpreter, before JAX is imported).

Run as ``python tests/_gang_check.py <case>``; prints ``GANG_OK <case>``.

Tolerances, relative to the largest entry:

- gang against the one-device streamed solve, 2e-6: the same kernels on
  the same rows, but the column sums add the four devices' fp32 partials
  in another order (the psum), a few ulp a sum, carried through the
  iterations;
- either against ``bench/reference_blocked.py`` (or ``bench/reference.py``),
  5e-6: the reference keeps the factors u and v and rounds them, where
  the program keeps and rounds the coupling, once an iteration each.
"""
import os
import sys

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"

import pathlib  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import reference, reference_blocked  # noqa: E402
from repro.core import UOTConfig  # noqa: E402
from repro.core.distributed import (  # noqa: E402
    rowsharded_fused_solver, shard_inputs)
from repro.kernels import ops  # noqa: E402

M, N, D = 512, 384, 4
REG = 0.05
GANG_TOL, REF_TOL = 2e-6, 5e-6


def problem(seed: int):
    """Points of the unit square, the Gibbs kernel of half their squared
    distance, marginals of mass 1 and 1.2."""
    rng = np.random.default_rng(seed)
    x = rng.uniform(size=(M, 2)).astype(np.float32)
    y = rng.uniform(size=(N, 2)).astype(np.float32)
    a = rng.uniform(0.5, 1.5, M).astype(np.float32)
    b = rng.uniform(0.5, 1.5, N).astype(np.float32)
    a, b = a / a.sum(), b / b.sum() * np.float32(1.2)
    K = np.asarray(reference_blocked.gibbs(jnp.asarray(x), jnp.asarray(y),
                                           REG))
    return x, y, K, a, b


def split_problem(seed: int):
    """A random-cost problem in which the first device's rows and the
    first quarter of the columns form a nearly separate block whose cost
    is nearly flat: those rows settle within a few iterations, the rest
    take many, so the devices' own drifts cross ``tol`` apart."""
    rng = np.random.default_rng(seed)
    C = rng.uniform(size=(M, N)).astype(np.float32)
    r, c = M // D, N // 4
    C[:r, :c] *= np.float32(0.01)
    K = np.exp(-C / np.float32(REG))
    K[:r, c:] *= np.float32(1e-6)
    K[r:, :c] *= np.float32(1e-6)
    a = rng.uniform(0.5, 1.5, M).astype(np.float32)
    b = rng.uniform(0.5, 1.5, N).astype(np.float32)
    return K, a / a.sum(), b / b.sum() * np.float32(1.2)


def rel(x, ref) -> float:
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(x - ref).max() / np.abs(ref).max())


def solve_both(K, a, b, cfg):
    """(gang, one device), each ``(P, colsum, iters)`` as numpy."""
    mesh = jax.make_mesh((D,), ("rows",))
    gang = rowsharded_fused_solver(mesh, "rows", cfg, impl="kernel")
    sK, sa, sb = shard_inputs(mesh, "rows", jnp.asarray(K), jnp.asarray(a),
                              jnp.asarray(b))
    Pg, cg, ig = gang(sK, sa, sb)
    assert Pg.sharding.spec == jax.sharding.PartitionSpec("rows", None)
    P1, c1, i1 = ops.streamed_solve(
        jnp.asarray(K)[None], jnp.asarray(a)[None], jnp.asarray(b)[None],
        cfg, impl="kernel", interpret=True)
    return ((np.asarray(Pg), np.asarray(cg), int(ig)),
            (np.asarray(P1[0]), np.asarray(c1[0]), int(i1)))


def blocked(x, y, a, b, cfg):
    u, v, colsum, iters, _ = reference_blocked.solve(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(a), jnp.asarray(b),
        reg=REG, exponent=reference.fi(cfg.reg, cfg.reg_m), tol=cfg.tol,
        num_iters=cfg.num_iters, block=128)
    P = reference_blocked.coupling(jnp.asarray(x), jnp.asarray(y), u, v,
                                   reg=REG)
    return np.asarray(P), np.asarray(colsum), int(iters)


def check_gang(cfg, seeds=(0, 1, 2)):
    for seed in seeds:
        x, y, K, a, b = problem(seed)
        (Pg, cg, ig), (P1, c1, i1) = solve_both(K, a, b, cfg)
        Pr, cr, ir = blocked(x, y, a, b, cfg)
        assert ig == i1 == ir, (seed, ig, i1, ir)
        assert rel(Pg, P1) <= GANG_TOL, (seed, rel(Pg, P1))
        assert rel(cg, c1) <= GANG_TOL, (seed, rel(cg, c1))
        assert rel(Pg, Pr) <= REF_TOL, (seed, rel(Pg, Pr))
        assert rel(cg, cr) <= REF_TOL, (seed, rel(cg, cr))
    return ig


def first_crossings(K, a, b, cfg) -> list[int]:
    """For each device's rows, the first iteration whose row factors
    moved by at most ``tol`` there (the reference's iteration)."""
    A, prev = jnp.asarray(K), jnp.ones(M)
    out = [None] * D
    for t in range(1, cfg.num_iters + 1):
        A, frow = reference.iterate(A, jnp.asarray(a), jnp.asarray(b),
                                    reference.fi(cfg.reg, cfg.reg_m))
        drift = np.abs(np.asarray(frow - prev)).reshape(D, -1).max(axis=1)
        prev = frow
        for d in range(D):
            if out[d] is None and drift[d] <= cfg.tol:
                out[d] = t
        if all(out):
            return out
    raise AssertionError(f"no crossing within {cfg.num_iters}: {out}")


def main(case: str):
    assert jax.device_count() == D, jax.device_count()
    if case == "tol":
        iters = check_gang(UOTConfig(reg=REG, reg_m=1.0, num_iters=1000,
                                     tol=1e-4))
        assert 1 < iters < 1000, iters
    elif case == "fixed":
        assert check_gang(UOTConfig(reg=REG, reg_m=1.0, num_iters=9,
                                    tol=None)) == 9
    elif case == "drifts_cross_apart":
        cfg = UOTConfig(reg=REG, reg_m=1.0, num_iters=1000, tol=1e-4)
        K, a, b = split_problem(3)
        crossings = first_crossings(K, a, b, cfg)
        assert crossings[0] < min(crossings[1:]), crossings
        print(f"first crossings by device: {crossings}")
        (Pg, cg, ig), (P1, c1, i1) = solve_both(K, a, b, cfg)
        assert ig == i1 == max(crossings), (ig, i1, crossings)
        assert rel(Pg, P1) <= GANG_TOL and rel(cg, c1) <= GANG_TOL
    elif case == "references_agree":
        for seed, tol in ((4, 1e-4), (5, None)):
            cfg = UOTConfig(reg=REG, reg_m=1.0, num_iters=1000 if tol
                            else 7, tol=tol)
            x, y, K, a, b = problem(seed)
            P, colsum, iters, _ = reference.solve(
                jnp.asarray(K), jnp.asarray(a), jnp.asarray(b),
                exponent=reference.fi(cfg.reg, cfg.reg_m), tol=cfg.tol,
                num_iters=cfg.num_iters)
            Pr, cr, ir = blocked(x, y, a, b, cfg)
            assert int(iters) == ir, (seed, int(iters), ir)
            assert rel(Pr, P) <= REF_TOL, (seed, rel(Pr, P))
            assert rel(cr, colsum) <= REF_TOL, (seed, rel(cr, colsum))
    elif case == "span_and_counters":
        from repro.core.distributed import gang_solve_sharded
        from repro.obs import Observability, gang_collective_bytes
        cfg = UOTConfig(reg=REG, reg_m=1.0, num_iters=1000, tol=1e-4)
        _, _, K, a, b = problem(6)
        mesh = jax.make_mesh((D,), ("rows",))
        sK, sa, sb = shard_inputs(mesh, "rows", jnp.asarray(K),
                                  jnp.asarray(a), jnp.asarray(b))
        obs = Observability(chain=False)
        counts = [gang_solve_sharded(mesh, "rows", sK, sa, sb, cfg,
                                     obs=obs)[2] for _ in range(2)]
        reg = obs.registry
        assert reg.histogram("profile.phase.gang.solve").count == 2
        assert reg.counter("gang.iters").value == sum(counts), counts
        assert reg.counter("gang.allreduce_bytes").value == (
            gang_collective_bytes(N, sum(counts)))
    else:
        raise SystemExit(f"unknown case {case!r}")
    print(f"GANG_OK {case}")


if __name__ == "__main__":
    main(sys.argv[1])
