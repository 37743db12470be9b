"""Byte counts of the solve, for the roofline shares.

``least_solve_bytes`` is the work any schedule of the matrix-scaling
iteration has to do: read each element of the M x N coupling once per
iteration, in its storage dtype. It does not depend on how the program
moves the bytes, so a share computed from it reads the same whatever
implements the solve, and a schedule that moves more bytes shows as a
lower share: MAP-UOT's fused schedule, one read and one write per
iteration, moves twice these bytes.
"""
from __future__ import annotations


def least_solve_bytes(M: int, N: int, itemsize: int, iters: int) -> int:
    """One read of every coupling element per iteration."""
    return iters * M * N * itemsize

