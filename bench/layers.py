"""Arithmetic the per-layer readers share. Each returns None where the
run gave it nothing to read."""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str, key: str) -> float:
    """A peak of one chip from ``peaks.json``; an unknown chip is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind][key]


def idle_pct(run) -> float | None:
    s = run.trace_summary
    if not s or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])


def round_ms(run) -> float | None:
    steps = run.facts.get("steps")
    if not steps:
        return None
    return 1000.0 * run.facts["step_s"] / steps


def solve_roofline(run) -> float | None:
    """Least bytes per solve over device busy time per solve inside the
    solve spans, as a share of the chip's HBM bandwidth."""
    s = run.trace_summary
    busy = (s or {}).get("busy_in", {}).get("bench.solve", 0.0)
    solves = run.facts.get("solves")
    if not busy or not solves:
        return None
    bw = peak(run.devices[0].device_kind, "hbm_bytes_per_s")
    return 100.0 * run.facts["least_bytes_per_solve"] / (
        busy / solves) / bw
