"""The one traffic generator: it turns a traffic mix file
(``bench/traffic/<mix>.json``) and a seed into the requests of a run.

A mix states a fixed composition: every pair of sides ``(M, N)`` from
``sides`` with every entry of ``kinds`` (``{"kind": "dense" | "points",
"d": dims, "count": n}``). Each request's problem is fixed by its place
in the composition (``base``). The seed permutes the requests and the
rows and columns of each problem; it never changes which problems a run
holds, so the work is the same.

``"loop": "closed"``: ``distinct_units`` copies of the composition,
shuffled, that ``clients`` clients take in turn, each sending its next
request when its last one came back.
"""
from __future__ import annotations

import dataclasses

from bench.data import rng_from_seed


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    kind: str            # "dense" or "points"
    M: int
    N: int
    d: int | None        # point dimension; None for dense
    base: int = 0        # draws the problem (bench.data.request_arrays)

    @property
    def shape_key(self) -> tuple:
        return (self.kind, self.d, self.M, self.N)

    @property
    def elements(self) -> int:
        return self.M * self.N


def composition(mix: dict) -> list[RequestSpec]:
    """One unit of the mix, in a fixed order (``base`` left at 0)."""
    out = []
    for M in mix["sides"]:
        for N in mix["sides"]:
            for k in mix["kinds"]:
                out += [RequestSpec(k["kind"], M, N, k.get("d"))] * k["count"]
    return out


def numbered(specs: list[RequestSpec]) -> list[RequestSpec]:
    """Each request with its own ``base``: its place before the shuffle."""
    return [dataclasses.replace(s, base=i) for i, s in enumerate(specs)]


def closed_loop(mix: dict, seed: int) -> list[RequestSpec]:
    """The distinct requests the clients of a closed loop take in turn."""
    specs = numbered(composition(mix) * mix["distinct_units"])
    rng = rng_from_seed(seed, 1)
    return [specs[i] for i in rng.permutation(len(specs))]


def requests(mix: dict, seed: int, seconds: float) -> list[RequestSpec]:
    if mix["loop"] == "closed":
        return closed_loop(mix, seed)
    raise ValueError(f"unknown loop {mix['loop']!r}")

