"""Benchmark harness: one module per paper table/figure (+ beyond-paper).

Prints ``name,us_per_call,derived`` CSV and writes one ``BENCH_<suite>.json``
per suite (into --out-dir, default cwd) so the perf trajectory accumulates
across PRs. Each suite also gets an ``OBS_<suite>.json`` — the
process-global observability dump (``repro.obs.global_dump``: registry
counters/gauges/histograms + the HBM-traffic accountant's per-route byte
totals and roofline summary), reset between suites so each file describes
one suite's work. Both payloads carry a ``meta`` provenance block
(``common.bench_meta``: schema version, git sha, jax versions, machine
fingerprint); ``--check`` re-reads the committed ``BENCH_<suite>.json``
from ``--baseline-dir`` before writing and fails the run when any record
regresses past ``--threshold`` (default 1.3x) on the same machine —
cross-machine comparisons are skipped, not judged. Mapping to the paper:
  bench_uot          -> Fig 9/10 (CPU single/multi-thread performance)
  bench_traffic      -> Fig 11  (cache misses -> HBM traffic)
  bench_kernel       -> Fig 8/13/14 (GPU tiling/perf/throughput -> TPU roofline)
  bench_memory       -> Fig 15  (peak memory consumption)
  bench_distributed  -> Fig 16  (Tianhe-1 scaling -> pod scaling)
  bench_application  -> Fig 17  (color-transfer application)
  bench_moe_router   -> beyond-paper (Sinkhorn-UOT MoE routing)
  bench_batch        -> beyond-paper (batched serving: fused stack vs loop)
  bench_serve        -> beyond-paper (continuous scheduler vs flush barrier
                        on a Poisson arrival trace; BENCH_SERVE_SMOKE=1
                        shrinks it to a CI smoke run)
  bench_resident     -> beyond-paper (VMEM-resident whole-solve fusion vs
                        per-iteration streamed launches;
                        BENCH_RESIDENT_SMOKE=1 for the CI smoke run)
  bench_geometry     -> beyond-paper (implicit cost geometries: coordinate
                        payloads + on-chip cost tiles vs host-materialized
                        dense C; BENCH_GEOMETRY_SMOKE=1 for the CI smoke
                        run)
  bench_cluster      -> beyond-paper (multi-device serving: 8 sharded lane
                        pool devices vs the 1-device scheduler, measured
                        -service DES; BENCH_CLUSTER_SMOKE=1 for the CI
                        smoke run on 8 forced host devices)
  bench_chaos        -> beyond-paper (fault-containment chaos harness: NaN
                        payloads + overflow configs + a device blackout
                        through the 8-device scheduler; hard-asserts zero
                        lost requests, zero span loss in the exported
                        JSONL trace, traffic totals that match the
                        dispatch-table formulas, bit-identical healthy
                        results, and goodput >= 0.9x fault-free;
                        BENCH_CHAOS_SMOKE=1 for the CI smoke run)
  bench_obs          -> beyond-paper (observability overhead: the
                        bench_serve scheduler DES with the obs bundle
                        enabled vs disabled; hard-asserts <= 5% overhead
                        on throughput and p99; BENCH_OBS_SMOKE=1 for the
                        CI smoke run)
  bench_overload     -> beyond-paper (overload robustness: 3x-capacity
                        Poisson burst, predictive admission + degrade
                        ladder vs the drop-policy baseline on a simulated
                        clock; hard-asserts zero lost requests, zero SLO
                        misses among full-quality completions, labeled
                        degrades, goodput >= 1.5x the baseline, and a 12x
                        spike escalating into the sliced 1-D tier;
                        BENCH_OVERLOAD_SMOKE=1 for the CI smoke run)
"""
import argparse
import json
import pathlib
import platform
import sys
import traceback

import jax


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--out-dir", default=".",
                        help="directory for BENCH_<suite>.json files")
    parser.add_argument("--suite", action="append", default=None,
                        help="run only these suites (repeatable), e.g. "
                             "--suite bench_batch")
    parser.add_argument("--check", action="store_true",
                        help="after each suite, compare its fresh records "
                             "against the committed baseline in "
                             "--baseline-dir (common.check_payload); exit "
                             "1 on any regression")
    parser.add_argument("--baseline-dir", default=".",
                        help="directory holding baseline BENCH_<suite>.json "
                             "files for --check (default: cwd)")
    parser.add_argument("--threshold", type=float, default=1.3,
                        help="per-record slowdown ratio that counts as a "
                             "regression for --check (default 1.3)")
    args = parser.parse_args(argv)

    from repro import obs as obslib
    from repro.launch import compile_cache
    compile_cache.enable(pathlib.Path(__file__).resolve().parent.parent)
    from benchmarks import (common, bench_uot, bench_traffic, bench_kernel,
                            bench_memory, bench_distributed,
                            bench_application, bench_moe_router, bench_batch,
                            bench_serve, bench_resident, bench_geometry,
                            bench_cluster, bench_chaos, bench_obs,
                            bench_overload)
    mods = [bench_uot, bench_traffic, bench_kernel, bench_memory,
            bench_distributed, bench_application, bench_moe_router,
            bench_batch, bench_serve, bench_resident, bench_geometry,
            bench_cluster, bench_chaos, bench_obs, bench_overload]
    if args.suite:
        known = {m.__name__.split(".")[-1] for m in mods}
        unknown = set(args.suite) - known
        if unknown:
            parser.error(f"unknown suite(s) {sorted(unknown)}; "
                         f"known: {sorted(known)}")
        mods = [m for m in mods if m.__name__.split(".")[-1] in args.suite]

    out_dir = pathlib.Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    baseline_dir = pathlib.Path(args.baseline_dir)
    meta = common.bench_meta()
    print("name,us_per_call,derived")
    failed = 0
    regressed = 0
    for mod in mods:
        suite = mod.__name__.split(".")[-1]
        json_path = out_dir / f"BENCH_{suite}.json"
        obs_path = out_dir / f"OBS_{suite}.json"
        # read the baseline BEFORE writing the fresh payload — --check
        # with out-dir == baseline-dir must not clobber-then-compare
        baseline = None
        if args.check:
            bpath = baseline_dir / f"BENCH_{suite}.json"
            if bpath.exists():
                baseline = json.loads(bpath.read_text())
        common.reset_records()
        # zero the process-global registry + traffic accountant so the
        # suite's OBS dump describes this suite's work only
        obslib.reset_global()
        try:
            mod.run()
        except Exception:
            failed += 1
            print(f"{mod.__name__},-1,FAILED", file=sys.stderr)
            traceback.print_exc()
            # don't let a stale JSON from an earlier run masquerade as
            # this run's result
            json_path.unlink(missing_ok=True)
            obs_path.unlink(missing_ok=True)
            continue
        payload = {
            "suite": suite,
            "backend": jax.default_backend(),
            "platform": platform.platform(),
            "meta": meta,
            "records": common.reset_records(),
        }
        json_path.write_text(json.dumps(payload, indent=2) + "\n")
        obs_path.write_text(
            json.dumps({"suite": suite, "meta": meta,
                        **obslib.global_dump()}, indent=2)
            + "\n")
        if args.check:
            if baseline is None:
                print(f"check {suite}: SKIP (no baseline in "
                      f"{baseline_dir})", file=sys.stderr)
                continue
            verdict = common.check_payload(payload, baseline,
                                           threshold=args.threshold)
            if verdict["status"] == "skip":
                print(f"check {suite}: SKIP ({verdict['reason']})",
                      file=sys.stderr)
            elif verdict["status"] == "fail":
                regressed += 1
                for f in verdict["failures"]:
                    print(f"check {suite}: REGRESSION {f['name']} "
                          f"{f['baseline_us']} -> {f['fresh_us']} us "
                          f"({f['ratio']}x > {args.threshold}x)",
                          file=sys.stderr)
            else:
                print(f"check {suite}: OK ({verdict['compared']} records "
                      f"within {args.threshold}x)", file=sys.stderr)
    if failed or regressed:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
