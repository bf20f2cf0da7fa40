"""Operations and bytes the work requires, from shapes alone, and the
table of peaks. Nothing here reads ``cost_analysis``: every implementation
of a piece of work is counted against the same number."""
from __future__ import annotations

import json
import math
import os

from references.topologies import degree

LANE = 128


def peaks(device_kind: str, bench_dir: str) -> dict:
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" in peaks.json; known: {sorted(table)}")
    return table[device_kind]


def sublane_rows(itemsize: int) -> int:
    return max(8, 32 // itemsize)


def gossip_mix_work(traffic: dict, replica_elements: int, itemsize: int,
                    chips: int) -> tuple[float, float]:
    """(FLOPs, bytes) per chip per step of the fused mix + update.

    Each worker's parameters sit in a flat bus of 128-lane rows, padded to
    whole sublane tiles. The mix reads its own rows, those of each of its
    ``k`` neighbours and the update, and writes the result: (k + 3) passes
    over the bus, k + 1 multiply-adds and one for the update per element.
    """
    k = degree(traffic["topology"], traffic["workers"])
    sub = sublane_rows(itemsize)
    rows = math.ceil(math.ceil(replica_elements / LANE) / sub) * sub
    per_worker = rows * LANE
    workers_per_chip = traffic["workers"] / chips
    elems = per_worker * workers_per_chip
    return 2.0 * (k + 2) * elems, float((k + 3) * elems * itemsize)
