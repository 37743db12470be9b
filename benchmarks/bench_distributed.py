"""Paper Fig. 16 analog: multi-node scaling (Tianhe-1 -> TPU pod).

Runs the shard_map row-sharded solver on 2/4/8 forced host devices,
checking correctness and counting the collectives per iteration. Each
rank count runs in a child process pinned to the CPU in its own
environment (``JAX_PLATFORMS=cpu`` + the forced device count), so a
parent that holds a chip never has a child reach for it; a child that
fails raises. These are correctness checks on the CPU backend: their
times say nothing about a TPU.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

from benchmarks.common import emit

ROOT = pathlib.Path(__file__).resolve().parent.parent

_CHILD = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from repro.core import UOTConfig, sinkhorn_uot_fused
from repro.core.distributed import rowsharded_fused_solver, shard_inputs
import time

M = N = 2048
rng = np.random.default_rng(0)
K = jnp.asarray(np.exp(-rng.uniform(0, 1, (M, N)) / 0.05), jnp.float32)
a = jnp.asarray(rng.uniform(0.5, 1.5, M).astype(np.float32))
b = jnp.asarray(rng.uniform(0.5, 1.5, N).astype(np.float32))
cfg = UOTConfig(reg=0.05, reg_m=1.0, num_iters=20)
mesh = jax.make_mesh((%(p)d,), ("rows",))
solver = rowsharded_fused_solver(mesh, "rows", cfg)
sA, sa, sb = shard_inputs(mesh, "rows", K, a, b)
ref, _ = sinkhorn_uot_fused(K, a, b, cfg)
A, _, _ = solver(sA, sa, sb)
ok = bool(jnp.allclose(A, ref, rtol=3e-5, atol=1e-8))
jax.block_until_ready(solver(sA, sa, sb))
t0 = time.perf_counter(); jax.block_until_ready(solver(sA, sa, sb))
dt = time.perf_counter() - t0
hlo = jax.jit(solver).lower(sA, sa, sb).compile().as_text()
n_ar = hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")
print(json.dumps({"ok": ok, "sec": dt, "allreduce_ops": n_ar}))
"""


def run():
    for p in (2, 4, 8):
        env = dict(os.environ)
        env["PYTHONPATH"] = str(ROOT / "src")
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={p}"
        out = subprocess.run([sys.executable, "-c", _CHILD % {"p": p}],
                             capture_output=True, text=True, env=env,
                             timeout=600)
        if out.returncode != 0:
            raise RuntimeError(f"p={p} child exited {out.returncode}:\n"
                               f"{out.stderr[-2000:]}")
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        if not rec["ok"]:
            raise RuntimeError(f"p={p}: row-sharded solve disagrees with "
                               f"the single-device reference")
        emit(f"dist_rowsharded_p{p}_2048", rec["sec"] / 20 * 1e6,
             f"cpu_host_devices_allreduce_ops={rec['allreduce_ops']}")
