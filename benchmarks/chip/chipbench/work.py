"""Work of the GBDT scoring problem, counted from its shapes.

CARAT scores ``n`` clients against ``c`` candidate configurations with an
oblivious model of ``T`` trees of depth ``D`` over ``n_h`` client and
``n_t`` candidate features. The problem needs one comparison per level
and one addition per tree for each (client, candidate) pair, and reads
each client row, the candidate grid and the model tables once. How a
scorer implements it (a padded one-hot matmul, a host cross product, a
factorized split) does not change this count, so a factorized scorer
reads the same work.
"""
from __future__ import annotations

F32 = 4
I32 = 4


def gbdt_ops(n: int, c: int, n_trees: int, depth: int) -> float:
    return float(n) * c * n_trees * (depth + 1)


def gbdt_bytes(n: int, c: int, n_trees: int, depth: int, n_h: int,
               n_t: int) -> float:
    rows = float(n) * n_h * F32 + float(c) * n_t * F32
    tables = n_trees * depth * (I32 + F32) + n_trees * (1 << depth) * F32
    return rows + tables + F32


def min_seconds(ops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak operations per second and bytes over peak memory bandwidth."""
    return max(ops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
