"""The traffic generator: the same seed gives the same requests; another
seed gives another order and other data, but the same composition and
the same amount of work."""
import collections
import json

import numpy as np
import pytest

from conftest import REPO
from bench import data, generator

MIXES = {p.stem: json.loads(p.read_text())
         for p in (REPO / "bench" / "traffic").glob("*.json")}
STREAMS = sorted(k for k, m in MIXES.items() if m["loop"] in ("open",
                                                               "closed"))
SEEDS = (1, 2**31 + 5, 2**40 + 3)


def _arrays(mix, seed, specs, n=8):
    rng = data.rng_from_seed(seed, 2)
    return [data.request_arrays(rng, s, {"mass_b": 1.1}, 0.05)
            for s in specs[:n]]


@pytest.mark.parametrize("mix", STREAMS)
def test_same_seed_same_requests(mix):
    a = generator.requests(MIXES[mix], 12345, 10)
    b = generator.requests(MIXES[mix], 12345, 10)
    assert a == b
    for x, y in zip(_arrays(mix, 12345, a), _arrays(mix, 12345, b)):
        assert x.keys() == y.keys()
        assert all(np.array_equal(x[k], y[k]) for k in x)


@pytest.mark.parametrize("mix", STREAMS)
def test_other_seeds_same_composition_and_work(mix):
    runs = [generator.requests(MIXES[mix], s, 10) for s in SEEDS]
    comps = [collections.Counter(r.shape_key for r in rs) for rs in runs]
    assert all(c == comps[0] for c in comps)
    bases = [sorted((r.base, r.shape_key) for r in rs) for rs in runs]
    assert all(b == bases[0] for b in bases)
    work = [sum(r.elements for r in rs) for rs in runs]
    assert len(set(work)) == 1
    orders = [[r.shape_key for r in rs] for rs in runs]
    assert orders[0] != orders[1]
    first = [_arrays(mix, s, rs, 1)[0]["a"] for s, rs in zip(SEEDS, runs)]
    assert not np.array_equal(first[0], first[1])


@pytest.mark.parametrize("kind,d", [("dense", None), ("points", 3)])
def test_a_request_is_its_base_problem_permuted_by_the_seed(kind, d):
    """The same base problem under two seeds: the same values in another
    row and column order, so the same solve."""
    from bench import reference
    spec = generator.RequestSpec(kind, 64, 96, d, base=17)
    arrs = [data.request_arrays(data.rng_from_seed(s, 2), spec,
                                {"mass_b": 1.1}, 0.05) for s in SEEDS[:2]]
    for k in arrs[0]:
        assert not np.array_equal(arrs[0][k], arrs[1][k])
        np.testing.assert_array_equal(np.sort(arrs[0][k], axis=None),
                                      np.sort(arrs[1][k], axis=None))
    kw = dict(exponent=reference.fi(0.05, 1.0), tol=1e-4, num_iters=300)
    if kind == "dense":
        iters = [int(reference.solve(x["K"], x["a"], x["b"], **kw)[2])
                 for x in arrs]
    else:
        iters = [int(reference.solve_points_batch(
            x["x"][None], x["y"][None], x["a"][None], x["b"][None],
            scale=float(d), reg=0.05, **kw)[2][0]) for x in arrs]
    assert iters[0] == iters[1]


def test_composition_is_every_pair_of_sides_with_every_kind():
    mix = MIXES["small"]
    comp = generator.composition(mix)
    per = sum(k["count"] for k in mix["kinds"])
    assert len(comp) == len(mix["sides"]) ** 2 * per
    kinds = collections.Counter((r.kind, r.d) for r in comp)
    assert kinds == {("dense", None): 2 * 9, ("points", 3): 9,
                     ("points", 32): 9}


def test_wide_seeds_keep_their_high_bits():
    import jax
    k1 = jax.random.key_data(data.key_from_seed(5))
    k2 = jax.random.key_data(data.key_from_seed(2**40 + 5))
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))


def test_solve_cell_seeds_permute_one_problem():
    """Every seed solves the same dense problem in another row and
    column order, so the reference stops after the same iterations."""
    from bench import reference
    spec = {"M": 96, "N": 160, "mass_b": 1.2}
    runs = [data.gibbs_2d(s, spec, 0.05) for s in (3, 2**31 + 7)]
    (K1, a1, b1), (K2, a2, b2) = runs
    assert not np.array_equal(np.asarray(K1), np.asarray(K2))
    np.testing.assert_array_equal(np.sort(np.asarray(K1), axis=None),
                                  np.sort(np.asarray(K2), axis=None))
    np.testing.assert_allclose(np.sort(np.asarray(a1)),
                               np.sort(np.asarray(a2)), rtol=1e-6)
    iters = [int(reference.solve(K, a, b, exponent=reference.fi(0.05, 1.0),
                                 tol=1e-4, num_iters=1000)[2])
             for K, a, b in runs]
    assert iters[0] == iters[1]
