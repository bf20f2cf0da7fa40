"""Mixing matrices by topology name, built from the graph's definition.

``A[i, j]`` is the weight worker j gives worker i's parameters; every
worker gives itself and each neighbour the same weight 1 / (degree + 1).
"""
from __future__ import annotations

import numpy as np


def _neighbours(name: str, M: int) -> list[set[int]]:
    if name == "ring":
        return [{(j - 1) % M, (j + 1) % M} - {j} for j in range(M)]
    if name == "clique":
        return [set(range(M)) - {j} for j in range(M)]
    raise KeyError(f"no mixing matrix for topology {name!r}")


def mixing_matrix(name: str, M: int) -> np.ndarray:
    A = np.zeros((M, M))
    for j, nbrs in enumerate(_neighbours(name, M)):
        for i in nbrs | {j}:
            A[i, j] = 1.0 / (len(nbrs) + 1)
    return A


def degree(name: str, M: int) -> int:
    """Neighbours each worker reads per mix (self not counted)."""
    return len(_neighbours(name, M)[0])
