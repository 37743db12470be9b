"""Packed, donated pool admission (``ops.lane_admit_packed``).

The serving scheduler lands each pool update as two host buffers and one
launch that writes the donated pool in place, building point-cloud Gibbs
tiles inside the launch. The reference here is the eager admission it
replaced — one transfer per field, the geometry's mirror and mask as
separate ops, the non-donating ``ops.lane_admit`` — and the pool must come
out bit-identical to it, with one compiled signature per payload kind.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import UOTConfig
from repro.geometry import PointCloudGeometry
from repro.kernels import ops
from repro.serve import UOTScheduler

CFG = UOTConfig(reg=0.05, reg_m=1.0, num_iters=30, tol=1e-6)
KINDS = [None, 3, 32]          # dense, then point clouds of dimension d


def eager_admit(state, placed, bucket, reg, d=None, scale=1.0):
    """The eager pool update: ``placed`` is [(lane, request)], padded to
    the pool size by repeating the last admission."""
    Mb, Nb = bucket
    L = state.num_lanes
    rows = [placed[min(j, len(placed) - 1)] for j in range(L)]
    lanes = np.array([lane for lane, _ in rows], np.int32)
    ap = np.zeros((L, Mb), np.float32)
    bp = np.zeros((L, Nb), np.float32)
    for j, (_, req) in enumerate(rows):
        M, N = req.shape
        ap[j, :M], bp[j, :N] = req.a, req.b
    if d is None:
        Kp = np.zeros((L, Mb, Nb), np.float32)
        for j, (_, req) in enumerate(rows):
            M, N = req.shape
            Kp[j, :M, :N] = req.K
        return ops.lane_admit(state, jnp.asarray(lanes), jnp.asarray(Kp),
                              jnp.asarray(ap), jnp.asarray(bp))
    xs = np.zeros((L, Mb, d), np.float32)
    xns = np.zeros((L, Mb), np.float32)
    ys = np.zeros((L, Nb, d), np.float32)
    yns = np.zeros((L, Nb), np.float32)
    mv = np.zeros(L, np.int32)
    nv = np.zeros(L, np.int32)
    for j, (_, req) in enumerate(rows):
        M, N = req.shape
        xs[j, :M], xns[j, :M] = req.x, req.xn
        ys[j, :N], yns[j, :N] = req.y, req.yn
        mv[j], nv[j] = M, N
    g = PointCloudGeometry(
        x=jnp.asarray(xs), y=jnp.asarray(ys), xn=jnp.asarray(xns),
        yn=jnp.asarray(yns), m_valid=jnp.asarray(mv),
        n_valid=jnp.asarray(nv), scale=scale)
    return ops.lane_admit(state, jnp.asarray(lanes), g.kernel(reg),
                          jnp.asarray(ap), jnp.asarray(bp))


class EagerScheduler(UOTScheduler):
    """A scheduler admitting through ``eager_admit``: the reference for
    what a served request returns."""

    def _admit_dense(self, bucket, placed):
        pool = self._pools[bucket]
        pool.state = eager_admit(pool.state, placed, bucket, self.cfg.reg)

    def _admit_points(self, bucket, placed, d, scale):
        pool = self._pools[bucket]
        pool.state = eager_admit(pool.state, placed, bucket, self.cfg.reg,
                                 d=d, scale=scale)


def submit(sched, kind, M, N, seed):
    rng = np.random.default_rng(seed)
    a = (rng.uniform(0.5, 1.5, M) / M).astype(np.float32)
    b = (rng.uniform(0.5, 1.5, N) / N * 1.2).astype(np.float32)
    if kind is None:
        K = np.exp(-rng.uniform(0, 1, (M, N)) / CFG.reg).astype(np.float32)
        return sched.submit(K, a, b)
    x = rng.uniform(0, 1, (M, kind)).astype(np.float32)
    y = rng.uniform(0, 1, (N, kind)).astype(np.float32)
    return sched.submit_points(x, y, a, b, scale=float(kind))


def groups(pool):
    """A pool's placed requests as the scheduler groups them per update."""
    out = {}
    for lane, req in sorted(pool.requests.items()):
        key = None if req.K is not None else (req.x.shape[1], req.scale)
        out.setdefault(key, []).append((lane, req))
    return out


@pytest.mark.parametrize("m_bucket,n_bucket", [(64, 128), (32, 64)])
def test_packed_admission_matches_eager_pool(m_bucket, n_bucket):
    """A mixed round's pool update, on a pool already mid-solve, leaves
    every LaneState field bit-identical to the eager path's, and donates
    the pool it was given."""
    sched = UOTScheduler(CFG, interpret=True, lanes_per_pool=8,
                         m_bucket=m_bucket, n_bucket=n_bucket)
    M, N = m_bucket - 5, n_bucket - 9
    for i, kind in enumerate([None, 3]):
        submit(sched, kind, M, N, seed=i)
    sched.step()                        # two lanes admitted and advanced
    (bucket, pool), = sched._pools.items()
    before = jax.tree.map(jnp.copy, pool.state)
    held = dict(pool.requests)
    for i, kind in enumerate([None, 3, 32, None, 32, 3]):
        submit(sched, kind, M - i, N - 2 * i, seed=10 + i)
    donated = pool.state
    sched._admit_queued()
    assert donated.P.is_deleted()
    want = before
    for key, placed in groups(pool).items():
        placed = [(l, r) for l, r in placed if l not in held]
        d, scale = (None, 1.0) if key is None else key
        want = eager_admit(want, placed, bucket, CFG.reg, d=d, scale=scale)
    for field in ops.LaneState.__dataclass_fields__:
        np.testing.assert_array_equal(np.asarray(getattr(pool.state, field)),
                                      np.asarray(getattr(want, field)),
                                      err_msg=field)


def test_donated_pool_serves_as_eager_pool():
    """``run()`` through the donated admission returns exactly the
    couplings and iteration counts of the eager admission."""
    new = UOTScheduler(CFG, interpret=True, lanes_per_pool=3)
    old = EagerScheduler(CFG, interpret=True, lanes_per_pool=3)
    shapes = [(50, 70), (50, 70), (30, 40), (50, 70)]
    rids = [(submit(new, kind, M, N, seed=10 + s),
             submit(old, kind, M, N, seed=10 + s))
            for s, (M, N) in enumerate(shapes) for kind in KINDS]
    got, want = new.run(), old.run()
    for rn, ro in rids:
        np.testing.assert_array_equal(got[rn], want[ro])
    it_new = {t.rid: t.iters for t in new.request_log}
    it_old = {t.rid: t.iters for t in old.request_log}
    assert [it_new[r] for r, _ in rids] == [it_old[r] for _, r in rids]


def test_one_signature_per_kind_and_three_calls_per_update():
    """Admitting 1, 2, ..., L requests of each kind into one pool compiles
    the packed entry once per kind, and each update costs at most three
    device calls (two transfers, one launch)."""
    L = 4
    sched = UOTScheduler(CFG, interpret=True, lanes_per_pool=L)
    entry = ops.lane_admit_packed
    sizes = []
    for k in range(1, L + 1):
        for kind in KINDS:
            for i in range(k):
                submit(sched, kind, 64 - 7 * i, 128 - 11 * i,
                       seed=100 * k + i)
            sched.run()
            assert len(sched._pools) == 1
        sizes.append(entry._cache_size())
    assert sizes[1:] == sizes[:1] * (L - 1), sizes
    reg = sched.obs.registry
    updates = reg.counter("serve.admit.updates").value
    calls = reg.counter("serve.admit.device_calls").value
    assert updates == len(KINDS) * L
    assert calls <= 3 * updates
