"""Roofline analysis from compiled dry-run artifacts.

Hardware constants: TPU v5e — 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI (per direction).

Terms (seconds, PER DEVICE — the post-SPMD HLO module is per-partition, so
cost_analysis numbers are already per device):
    T_comp = FLOPs / 197e12
    T_mem  = bytes_accessed / 819e9
    T_coll = collective_bytes_moved / 50e9

collective_bytes is parsed from the optimized HLO: for each all-reduce /
all-gather / reduce-scatter / all-to-all / collective-permute, bytes moved
per device are estimated from the per-partition result shape (all-reduce
counts 2x: reduce-scatter + all-gather phases of a ring).
"""
from __future__ import annotations

import dataclasses
import json
import re

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB
# HBM at 819 GB/s, 1,600 Gbit/s of interconnect over four links). A device
# that is not in this table has no roofline: ``peaks`` returns None.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}

# The dry-run target (a described v5e topology).
PEAK_FLOPS = PEAKS["TPU v5 lite"]["flops"]    # bf16 / chip
HBM_BW = PEAKS["TPU v5 lite"]["hbm_bw"]       # bytes/s / chip
ICI_BW = PEAKS["TPU v5 lite"]["ici_bw"]       # bytes/s / link


def peaks(device_kind: str | None) -> dict | None:
    """The published peaks of ``device_kind``, or None when unknown."""
    return PEAKS.get(device_kind)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# `%name = TYPE[dims]{layout} opcode(` — possibly tuple-typed
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum bytes moved per device by collective ops in an HLO module."""
    per_kind: dict[str, int] = {}
    counts: dict[str, int] = {}
    for m in _OP_RE.finditer(hlo_text):
        type_str, kind, phase = m.group(1), m.group(2), m.group(3)
        if phase == "-done":
            continue  # counted at -start
        b = _shape_bytes(type_str)
        if kind == "all-reduce":
            b *= 2  # ring: reduce-scatter + all-gather phases
        per_kind[kind] = per_kind.get(kind, 0) + b
        counts[kind] = counts.get(kind, 0) + 1
    return {"bytes_by_kind": per_kind, "counts": counts,
            "total_bytes": sum(per_kind.values())}


@dataclasses.dataclass
class RooflineTerms:
    flops: float
    hbm_bytes: float
    coll_bytes: float

    @property
    def t_comp(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_mem(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_coll(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_comp, "memory": self.t_mem,
                 "collective": self.t_coll}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        """Lower-bound step time (perfect overlap = max of the terms)."""
        return max(self.t_comp, self.t_mem, self.t_coll)

    def roofline_fraction(self) -> float:
        """Fraction of the binding roof actually 'used' by useful work:
        dominant-term share of the no-overlap sum (1.0 = single clean
        bottleneck, low = time smeared across terms)."""
        s = self.t_comp + self.t_mem + self.t_coll
        return self.t_bound / s if s else 0.0

    def as_dict(self) -> dict:
        return {
            "flops_per_dev": self.flops,
            "hbm_bytes_per_dev": self.hbm_bytes,
            "coll_bytes_per_dev": self.coll_bytes,
            "t_comp_s": self.t_comp,
            "t_mem_s": self.t_mem,
            "t_coll_s": self.t_coll,
            "bottleneck": self.bottleneck,
            "t_bound_s": self.t_bound,
        }


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE), D = tokens.

    For decode shapes D = global_batch (one token per sequence)."""
    n = cfg.param_count()
    if cfg.family == "moe":
        emb = cfg.padded_vocab * cfg.d_model * 2
        expert = cfg.num_experts * 3 * cfg.d_model * cfg.d_ff
        active = n - cfg.num_layers * expert \
            + cfg.num_layers * cfg.top_k * 3 * cfg.d_model * cfg.d_ff
        n = active
    if shape.kind == "decode":
        tokens = shape.global_batch
    else:
        tokens = shape.global_batch * shape.seq_len
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n * tokens


def analyze(cost: dict, hlo_text: str, cfg=None, shape=None,
            num_devices: int = 256) -> dict:
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(hlo_text)
    terms = RooflineTerms(flops, hbm, coll["total_bytes"])
    out = terms.as_dict()
    out["collectives"] = coll
    if cfg is not None and shape is not None:
        mf = model_flops(cfg, shape)
        out["model_flops_total"] = mf
        out["model_flops_per_dev"] = mf / num_devices
        out["useful_flops_ratio"] = (mf / num_devices) / flops if flops else 0.0
        # MFU bound implied by the roofline terms
        out["mfu_bound"] = (mf / num_devices / PEAK_FLOPS) / terms.t_bound \
            if terms.t_bound else 0.0
    return out
