"""The main-path Pallas kernels compile for a TPU v5e at real sizes.

Each test lowers a kernel for a described ``v5e:2x2`` topology and
compiles it with the chip's own compiler, which is installed here: no
chip is needed and nothing runs. The compiler refuses what interpret mode
cannot see, such as a block that is not aligned to the tiling or a kernel
that asks for more VMEM than its scoped limit. The blocks are the ones
``ops.pick_block_m`` and ``ops.resident_fits`` choose, so these tests
also hold the VMEM accounting in ``ops`` to what Mosaic allocates.

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU compiler, and only once a test has started.
The persistent compilation cache is off around each compile (an entry
written without a chip cannot be read back).
"""
import collections
import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.cluster import lanes as cluster_lanes
from repro.core import UOTConfig
from repro.kernels import (ops, uot_batched, uot_fused, uot_halfpass,
                           uot_resident, uot_uv_fused)
from repro.kernels.vmem import VMEM_LIMIT_BYTES

CFG = UOTConfig(reg=0.05, reg_m=1.0, num_iters=300, tol=1e-4)
POOL = (8, 1024, 1024)   # the scheduler's lane pool for 1024 x 1024 buckets


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; it must hold a kernel."""
    with _no_persistent_cache():
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


@pytest.mark.parametrize("n,dtype", [(8192, jnp.float32),
                                     (4096, jnp.bfloat16)])
def test_streamed_iteration_and_colsum(one_chip, n, dtype):
    bm = ops.pick_block_m(n, n, jnp.dtype(dtype).itemsize)
    assert ops.streamed_vmem_bytes(
        bm, n, jnp.dtype(dtype).itemsize) <= VMEM_LIMIT_BYTES

    def step(A, fcol, a):
        cs = uot_fused.colsum(A, block_m=bm)
        return uot_fused.fused_iteration(A, fcol * cs, a, fi=CFG.fi,
                                         block_m=bm)

    _compile(step, *_shapes(one_chip, ((n, n), dtype), ((n,), jnp.float32),
                            ((n,), jnp.float32)))


@pytest.mark.parametrize("kernel", ["rows", "cols", "uv", "materialize"])
def test_halfpass_and_uv_kernels(one_chip, kernel):
    n = 8192
    bm = ops.pick_block_m(n, n)
    fn = {
        "rows": lambda A, f: uot_halfpass.scale_rows_accum_cols(
            A, f, block_m=bm),
        "cols": lambda A, f: uot_halfpass.scale_cols_accum_rows(
            A, f, block_m=bm),
        "uv": lambda K, v: uot_uv_fused.uv_iteration(
            K, v, v, fi=CFG.fi, block_m=bm),
        "materialize": lambda K, v: uot_uv_fused.materialize_coupling(
            K, v, v, block_m=bm),
    }[kernel]
    _compile(fn, *_shapes(one_chip, ((n, n), jnp.float32),
                          ((n,), jnp.float32)))


def test_resident_solve_stack(one_chip):
    B, n = 8, 512
    assert ops.resident_fits(n, n, CFG)
    _compile(lambda A, a, b: uot_resident.resident_solve(
        A, a, b, fi=CFG.fi, num_iters=CFG.num_iters, tol=CFG.tol),
        *_shapes(one_chip, ((B, n, n), jnp.float32), ((B, n), jnp.float32),
                 ((B, n), jnp.float32)))


def _pool_shapes(sharding, lead=()):
    L, M, N = POOL
    f32, i32 = jnp.float32, jnp.int32
    return ops.LaneState(*_shapes(
        sharding, (lead + (L, M, N), f32), (lead + (L, N), f32),
        (lead + (L, M), f32), (lead + (L, N), f32), (lead + (L, M), f32),
        (lead + (L,), i32), (lead + (L,), bool), (lead + (L,), bool),
        (lead + (L,), i32), (lead + (L,), i32), (lead + (L,), bool)))


@pytest.mark.parametrize("kernel", ["resident_stepped", "frow"])
def test_scheduler_chunk_kernels(one_chip, kernel):
    L, M, N = POOL
    st = _pool_shapes(one_chip)
    if kernel == "resident_stepped":
        assert ops.resident_fits(M, N, CFG)
        _compile(lambda s: uot_resident.resident_stepped(
            s.P, s.colsum, s.frow, s.iters, s.converged, s.active, s.a,
            s.b, fi=CFG.fi, n_iters=10, num_iters=CFG.num_iters,
            tol=CFG.tol), st)
    else:
        bm = ops.pick_block_m(M, N)
        _compile(lambda s: uot_batched.batched_fused_iteration_frow(
            s.P, s.colsum, s.a, s.active, fi=CFG.fi, block_m=bm), st)


def test_resident_solve_pc(one_chip):
    M, N, d = 1024, 2048, 3
    assert ops.resident_fits(M, N, CFG, implicit=True)
    f32, i32 = jnp.float32, jnp.int32
    _compile(lambda x, xn, y, yn, a, b, mv, nv: uot_resident.resident_solve_pc(
        x, xn, y, yn, a, b, mv, nv, fi=CFG.fi, reg=CFG.reg,
        num_iters=CFG.num_iters, tol=CFG.tol),
        *_shapes(one_chip, ((1, M, d), f32), ((1, M), f32), ((1, N, d), f32),
                 ((1, N), f32), ((1, M), f32), ((1, N), f32), ((1,), i32),
                 ((1,), i32)))


def test_cluster_stepped_on_four_chips(topo):
    mesh = jax.sharding.Mesh(np.array(topo.devices), ("devices",))
    st = _pool_shapes(NamedSharding(mesh, P("devices")), lead=(4,))
    fn = cluster_lanes._cluster_stepped_fn(mesh, "devices", 10, CFG, None,
                                           False, "kernel")
    with _no_persistent_cache():
        compiled = fn.lower(st).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _full_size_copies(hlo: str, dims: str) -> collections.Counter:
    """Copies of an f32 array of ``dims`` in a compiled HLO module, by
    where they sit: ``body`` (a ``while`` body), ``entry`` or ``other``."""
    bodies = set(re.findall(r"body=%?([\w.-]+)", hlo))
    where, found = None, collections.Counter()
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.-]+) \(.*\{$", line)
        if head:
            where = ("entry" if head.group(1) else
                     "body" if head.group(2) in bodies else "other")
        elif re.search(rf"= f32\[{dims}\]\S* copy(-start)?\(", line):
            found[where] += 1
    return found


def test_streamed_solve_keeps_its_coupling_in_place(one_chip, monkeypatch):
    """The streamed solve that ``solve_fused(impl='auto')`` runs at 20480²
    copies its coupling nowhere: the iteration kernel writes in place, the
    loop's first pass writes the buffer the loop then owns, and the batch
    axis goes on and off inside the executable."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)  # the chip's defaults
    n = 20480
    assert not ops.resident_fits(n, n, CFG)
    compiled = _compile(
        lambda A, a, b: ops._solve_fused_one_streamed(A, a, b, CFG),
        *_shapes(one_chip, ((n, n), jnp.float32), ((n,), jnp.float32),
                 ((n,), jnp.float32)))
    copies = _full_size_copies(compiled.as_text(), rf"(1,)?{n},{n}")
    assert copies["body"] == 0, copies
    assert copies["entry"] == 0, copies


def test_scheduler_streamed_chunk_loop_copies_no_pool(one_chip):
    """The scheduler's streamed chunk carries the lane pool's couplings
    through its loop without a copy per iteration."""
    L, M, N = POOL
    compiled = _compile(lambda s: ops._solve_fused_stepped_streamed(
        s, 10, CFG, impl="kernel", interpret=False), _pool_shapes(one_chip))
    copies = _full_size_copies(compiled.as_text(), rf"{L},{M},{N}")
    assert copies["body"] == 0, copies


@pytest.mark.parametrize("d", [None, 3, 32], ids=["dense", "d3", "d32"])
def test_scheduler_admission_writes_the_pool_in_place(one_chip, d):
    """The scheduler's pool update, point-cloud Gibbs tiles built inside
    it, aliases every field of the donated pool to its output and copies
    no pool."""
    L, M, N = POOL
    f32, i32, _, _ = ops.admit_buffers(L, M, N, d)
    payload = _shapes(one_chip, (f32.shape, f32.dtype),
                      (i32.shape, i32.dtype))
    with _no_persistent_cache():
        compiled = ops.lane_admit_packed.lower(
            _pool_shapes(one_chip), *payload, bucket=(M, N), d=d,
            reg=CFG.reg, scale=float(d or 1)).compile()
    hlo = compiled.as_text()
    aliased = re.search(r"input_output_alias=\{.*", hlo).group(0)
    assert aliased.count("may-alias") == len(
        ops.LaneState.__dataclass_fields__), aliased
    assert not _full_size_copies(hlo, rf"{L},{M},{N}"), hlo


def _all_reduces(hlo: str) -> collections.Counter:
    """All-reduces in a compiled HLO module by (where, result shape), with
    ``where`` as in ``_full_size_copies``."""
    bodies = set(re.findall(r"body=%?([\w.-]+)", hlo))
    where, found = None, collections.Counter()
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.-]+) \(.*\{$", line)
        if head:
            where = ("entry" if head.group(1) else
                     "body" if head.group(2) in bodies else "other")
        else:
            op = re.search(r"= (\w+\[[\d,]*\])\S* all-reduce(-start)?\(", line)
            if op:
                found[where, op.group(1)] += 1
    return found


def test_gang_on_four_chips_reduces_once_an_iteration_and_copies_nothing(
        topo, one_chip, monkeypatch):
    """The ``gang80k`` cell's solve, 81920² over a 2x2 host: each chip runs
    the streamed loop on its (20480, 81920) row block, with one all-reduce
    of the fp32 column sums and one of the drift in the ``while`` body, and
    no copy of a row block anywhere. The one-device loop at that block
    holds no collective."""
    from repro.core.distributed import rowsharded_fused_solver
    monkeypatch.setattr(ops, "on_tpu", lambda: True)  # the chip's defaults
    n, rows = 81920, 81920 // 4
    mesh = jax.sharding.Mesh(np.array(topo.devices), ("devices",))
    specs = [((n, n), P("devices", None)), ((n,), P("devices")), ((n,), P())]
    args = [jax.ShapeDtypeStruct(s, jnp.float32,
                                 sharding=NamedSharding(mesh, p))
            for s, p in specs]
    with _no_persistent_cache():
        compiled = rowsharded_fused_solver(mesh, "devices", CFG).lower(
            *args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    reduces = _all_reduces(hlo)
    assert reduces["body", f"f32[1,{n}]"] == 1, reduces
    assert reduces["body", "f32[1]"] == 1, reduces
    assert sum(k for (w, _), k in reduces.items() if w == "body") == 2
    copies = _full_size_copies(hlo, rf"(1,)?{rows},{n}")
    assert copies["body"] == 0 and copies["entry"] == 0, copies

    one = _compile(
        lambda A, a, b: ops._solve_fused_one_streamed(A, a, b, CFG),
        *_shapes(one_chip, ((rows, n), jnp.float32), ((rows,), jnp.float32),
                 ((n,), jnp.float32)))
    assert not _all_reduces(one.as_text())


def test_streamed_solve_compiles_in_bfloat16(one_chip, monkeypatch):
    """bf16 storage at 20480²: the block ``pick_block_m`` chooses counts
    the tile cast back to bf16 before its store, so the frow kernel fits
    its scoped VMEM."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    n, cfg = 20480, UOTConfig(reg=0.05, reg_m=1.0, num_iters=300, tol=1e-4,
                              dtype=jnp.bfloat16)
    assert ops.pick_block_m(n, n, 2) == 128
    _compile(lambda A, a, b: ops._solve_fused_one_streamed(A, a, b, cfg),
             *_shapes(one_chip, ((n, n), jnp.bfloat16), ((n,), jnp.float32),
                      ((n,), jnp.float32)))
