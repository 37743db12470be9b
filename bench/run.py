#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine that holds the chips the cell
asks for. The cell's entry in ``BENCHMARK.json`` names its configuration
(``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<mix>.json``); ``bench/workloads/<cell>.json`` names its
driver (``bench/drivers/<driver>.py``) and the limits of its output
comparison; each per-layer metric is read by ``bench/metrics/<metric>.py``.
Adding a cell, a mix, a configuration or a metric adds files and entries.

One run: find the devices (exit 2 without the platform or the chips the
cell asks for), turn on JAX's persistent compilation cache in
``<checkout>/.jax_cache``, build the inputs from ``--seed``, warm up,
measure for ``--seconds``, compare the window's outputs with
``bench/reference.py``, and print as the last line of standard output one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
end-to-end metrics, or with ``--trace 1`` the per-layer ones), ``device``,
with ``--trace 1`` ``breakdown``, and last ``compared``: each number
compared with its limit. The same numbers and limits end standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def cell_metrics(bench: dict, cell: str) -> tuple[list, list]:
    """The end-to-end and per-layer metrics that ``cell`` reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    moves = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in moves)]
    return e2e, layer


def number(v):
    """A JSON number, or null for a value that is not finite."""
    return v if v is not None and math.isfinite(v) else None


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None, *, root: pathlib.Path = ROOT, platform: str = "tpu",
         cache: bool = True) -> int:
    args = parse(argv)
    from bench import harness, trace as tracing

    bench = harness.load_json(root / "BENCHMARK.json")
    try:
        cell = harness.load_cell(root, args.workload)
    except KeyError as e:
        harness.eprint(f"bench: {e.args[0]}; no result")
        return 2
    devices = harness.devices_for(cell.entry, platform)
    if devices is None:
        return 2
    if cache:
        from repro.launch import compile_cache
        os.environ.pop(compile_cache.ENV, None)   # keep it in the checkout
        compile_cache.enable(root)
    d = devices[0]
    print(f"device: {d.platform} {d.device_kind!r}, {len(devices)} found, "
          f"{cell.entry['chips']} used", flush=True)

    run = cell.new_run(seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), devices=devices,
                       t_start=T_START)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        run.trace_dir = trace_dir
        cell.driver.run(run)
        summary = None
        if trace_dir:
            summary = tracing.reduce(tracing.load(
                tracing.find_xplane(trace_dir)))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    run.trace_summary = summary

    e2e, layer = cell_metrics(bench, cell.entry["name"])
    values = {}
    if not args.trace:
        measured = dict(run.metrics, setup_s=run.setup_s,
                        peak_hbm_gb=run.memory_peak_bytes / 1e9)
        for m in e2e:
            values[m["name"]] = {"value": number(measured[m["name"]]),
                                 "unit": m["unit"]}
    else:
        for m in layer:
            reader = harness.load_module(
                root / "bench" / "metrics" / f"{m['name']}.py")
            v = reader.read(run)
            if v is not None:
                values[m["name"]] = {"value": number(v), "unit": m["unit"]}

    device = harness.device_facts(run)
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": values, "device": device}
    if args.trace and summary is not None:
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = {"device_ops": summary["ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["compared"] = {n: {"value": number(v), "limit": lim}
                          for n, v, lim in run.checks}
    print(f"setup_s {run.setup_s!r}", flush=True)
    for n, v, lim in run.checks:
        harness.eprint(f"compared {n} {v!r} limit {lim!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
