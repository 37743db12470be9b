"""Persistent measurement store + measurement-driven dispatch advice.

``KernelProfiler`` (``repro.obs.profile``) measures us/call per kernel
cell in one process; this module makes those measurements *durable* and
*actionable*:

* ``MeasurementStore`` — a JSON file of median us/call per cell, stamped
  with a hostname-free **machine fingerprint** (backend, device kind and
  count, jax/jaxlib versions, CPU model, arch). Loading a store recorded
  on a different machine raises ``MeasurementMismatch`` — cross-machine
  wall-clock comparison is meaningless, and silently mixing fingerprints
  is how perf data rots. Combined with ``repro.obs.traffic``'s modeled
  byte formulas each cell yields **achieved GB/s** and a **measured**
  roofline fraction (achieved over the device's HBM peak in
  ``launch.roofline.PEAKS``, None off that table) next to the
  modeled one — the paper's Fig-11 bandwidth story, finally measured
  instead of assumed.
* ``MeasuredDispatch`` — the advisor ``kernels/ops.py`` consults from
  ``impl='auto'`` (via ``ops.dispatch_advisor``): when BOTH tiers of a
  (kernel, shape, dtype, source) cell have steady-state data, route to
  the measured-faster tier (normalized us per lane-iteration, so cells
  recorded at different lane counts / iteration budgets still compare);
  otherwise return None and the static ``resident_fits`` budget decides,
  exactly as before. Advice can only choose among tiers the static
  semantics allow — a shape over the VMEM budget, or a sub-fp32 stepped
  pool, is never advised resident.

Store schema (version 1)::

    {"schema_version": 1,
     "fingerprint": {"id": "...", "backend": ..., "device_kind": ...,
                     "device_count": ..., "jax": ..., "jaxlib": ...,
                     "cpu": ..., "machine": ...},
     "cells": {"<kernel>|<MxN>|s<itemsize>|<impl>|<source>|L<lanes>|T<iters>":
               {"count": int, "median_us": float, "first_us": float}}}
"""
from __future__ import annotations

import hashlib
import json
import pathlib
import platform

from repro.launch.roofline import peaks
from repro.obs.traffic import chunk_bytes as _chunk_bytes
from repro.obs.traffic import solve_bytes as _solve_bytes
from repro.obs.profile import parse_cell_key

__all__ = ["SCHEMA_VERSION", "MeasurementMismatch", "machine_fingerprint",
           "MeasurementStore", "MeasuredDispatch"]

SCHEMA_VERSION = 1


class MeasurementMismatch(RuntimeError):
    """The store on disk was recorded on a different machine (or with a
    different schema) than the one asking for it."""


def _cpu_model() -> str:
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return platform.processor() or "unknown"


def machine_fingerprint() -> dict:
    """Hostname-free identity of this (machine, jax stack) pair. Two
    processes with equal fingerprints produce comparable wall-clock
    numbers; nothing here identifies the host by name."""
    import jax
    import jaxlib
    dev = jax.devices()[0]
    fp = {
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", "unknown"),
        "device_count": jax.device_count(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "cpu": _cpu_model(),
        "machine": platform.machine(),
    }
    fp["id"] = hashlib.sha1(
        json.dumps(fp, sort_keys=True).encode()).hexdigest()[:12]
    return fp


def _cell_bytes(p: dict) -> int | None:
    """Modeled bytes per call for a parsed cell key, from the traffic
    formulas. Implicit cells charge ``d=0`` coordinate bytes (the true
    ``(M+N)*d*4`` G-term is unknowable from the key and negligible next
    to the M*N coupling traffic it bounds from below)."""
    if p["kernel"] == "solve":
        return p["lanes"] * _solve_bytes(
            p["M"], p["N"], p["itemsize"], p["iters"], tier=p["impl"],
            source=p["source"], d=0 if p["source"] == "implicit" else None)
    if p["kernel"] == "chunk":
        return _chunk_bytes(
            p["lanes"], p["M"], p["N"], p["itemsize"], p["iters"],
            tier=p["impl"])
    return None


class MeasurementStore:
    """Median us/call per measurement cell, fingerprint-stamped.

    In-memory it is a plain dict of cells; ``save``/``load`` round-trip
    it through JSON. ``ingest`` merges a ``KernelProfiler``'s current
    cells (by key, replace — profiler cells are cumulative, so repeated
    ingests are idempotent, not double-counting).
    """

    def __init__(self, fingerprint: dict | None = None):
        self.fingerprint = (fingerprint if fingerprint is not None
                            else machine_fingerprint())
        self.cells: dict[str, dict] = {}

    # -- writing ----------------------------------------------------------
    def record(self, key: str, median_us: float, *, count: int = 1,
               first_us: float | None = None) -> None:
        self.cells[key] = {"count": int(count),
                           "median_us": float(median_us),
                           "first_us": first_us}

    def ingest(self, profiler) -> int:
        """Merge a profiler's cells (those with a steady-state median);
        returns how many cells now hold data."""
        for key, cell in profiler.cells().items():
            if cell.get("median_us") is not None:
                self.cells[key] = dict(cell)
        return len(self.cells)

    # -- persistence ------------------------------------------------------
    def to_json(self) -> dict:
        return {"schema_version": SCHEMA_VERSION,
                "fingerprint": self.fingerprint, "cells": self.cells}

    def save(self, path) -> None:
        pathlib.Path(path).write_text(
            json.dumps(self.to_json(), indent=2, sort_keys=True) + "\n")

    @classmethod
    def load(cls, path, *, fingerprint: dict | None = None,
             allow_mismatch: bool = False) -> "MeasurementStore":
        """Load a store, rejecting one recorded elsewhere: raises
        ``MeasurementMismatch`` unless the on-disk fingerprint id equals
        this machine's (or ``fingerprint=``'s), or ``allow_mismatch``."""
        data = json.loads(pathlib.Path(path).read_text())
        if data.get("schema_version") != SCHEMA_VERSION:
            raise MeasurementMismatch(
                f"{path}: schema_version {data.get('schema_version')!r} "
                f"!= {SCHEMA_VERSION}")
        want = fingerprint if fingerprint is not None else machine_fingerprint()
        got = data.get("fingerprint", {})
        if not allow_mismatch and got.get("id") != want["id"]:
            raise MeasurementMismatch(
                f"{path}: recorded on {got.get('id')!r} "
                f"({got.get('device_kind')}, jax {got.get('jax')}), this "
                f"machine is {want['id']!r} ({want['device_kind']}, jax "
                f"{want['jax']}) — wall-clock cells do not transfer")
        store = cls(fingerprint=got or want)
        store.cells = dict(data.get("cells", {}))
        return store

    # -- readback ---------------------------------------------------------
    def us_per_call(self, key: str) -> float | None:
        cell = self.cells.get(key)
        return cell["median_us"] if cell else None

    def _matching(self, *, kernel=None, M=None, N=None, itemsize=None,
                  impl=None, source=None):
        for key, cell in self.cells.items():
            if cell.get("median_us") is None:
                continue
            p = parse_cell_key(key)
            if ((kernel is None or p["kernel"] == kernel)
                    and (M is None or p["M"] == M)
                    and (N is None or p["N"] == N)
                    and (itemsize is None or p["itemsize"] == itemsize)
                    and (impl is None or p["impl"] == impl)
                    and (source is None or p["source"] == source)):
                yield p, cell

    def us_per_lane_iter(self, *, kernel, M=None, N=None, itemsize=None,
                         impl=None, source=None,
                         min_count: int = 1) -> float | None:
        """Count-weighted mean of ``median_us / (lanes * iters)`` over
        matching cells (None fields match anything) — the normalized
        cost that compares cells recorded at different lane counts /
        chunk budgets. None when no cell matches with enough samples."""
        num = den = 0.0
        for p, cell in self._matching(kernel=kernel, M=M, N=N,
                                      itemsize=itemsize, impl=impl,
                                      source=source):
            # count includes the compile call; steady samples are count-1
            n_steady = cell["count"] - 1
            if n_steady < min_count:
                continue
            w = float(n_steady)
            num += w * cell["median_us"] / max(p["lanes"] * p["iters"], 1)
            den += w
        return num / den if den else None

    def achieved(self) -> dict:
        """Per-cell achieved bandwidth from measured time over modeled
        bytes: ``{key: {median_us, modeled_bytes, achieved_gbps,
        measured_roofline_fraction}}``. The fraction is against the
        HBM peak of the fingerprint's ``device_kind`` in
        ``launch.roofline.PEAKS``; a device that is not in that table (a
        CPU host, an unlisted chip) has none: the fraction is None, "not
        measured"."""
        peak = peaks(self.fingerprint.get("device_kind"))
        out = {}
        for key, cell in self.cells.items():
            us = cell.get("median_us")
            if us is None or us <= 0:
                continue
            nbytes = _cell_bytes(parse_cell_key(key))
            if nbytes is None:
                continue
            gbps = nbytes / (us * 1e-6) / 1e9
            out[key] = {"median_us": us, "modeled_bytes": nbytes,
                        "achieved_gbps": gbps,
                        "measured_roofline_fraction": (
                            None if peak is None
                            else gbps / (peak["hbm_bw"] / 1e9))}
        return out


class MeasuredDispatch:
    """``impl='auto'`` advice from stored measurements.

    ``advise`` returns 'resident' / 'streamed' when both tiers of the
    cell have steady-state data, None otherwise (the caller's static
    budget then decides). ``margin`` biases toward the static choice:
    the measured tier must beat the other by that factor to flip.
    """

    def __init__(self, store: MeasurementStore, *, min_count: int = 1,
                 margin: float = 1.0):
        self.store = store
        self.min_count = min_count
        self.margin = margin

    def advise(self, *, M: int, N: int, itemsize: int,
               implicit: bool = False, stepped: bool = False) -> str | None:
        kernel = "chunk" if stepped else "solve"
        source = "implicit" if implicit else "dense"
        res = self.store.us_per_lane_iter(
            kernel=kernel, M=M, N=N, itemsize=itemsize, impl="resident",
            source=source, min_count=self.min_count)
        str_ = self.store.us_per_lane_iter(
            kernel=kernel, M=M, N=N, itemsize=itemsize, impl="streamed",
            source=source, min_count=self.min_count)
        if res is None or str_ is None:
            return None
        return "streamed" if str_ * self.margin < res else "resident"
