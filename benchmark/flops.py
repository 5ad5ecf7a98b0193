"""Nominal operation counts, the reference tester's (as in
``slate_tpu/obs/flops.py``, copied so that no change to the program can
move the yardstick): one real multiply-add counts 2.

- getrf (LU) n x n: 2 n^3 / 3;  potrf (Cholesky): n^3 / 3;
- each triangular solve of n x n against n x k: n^2 k, two per solve;
- gemm m x k by k x n: 2 m n k.
"""

from __future__ import annotations


def getrf(n: int) -> float:
    return 2.0 * n ** 3 / 3.0


def potrf(n: int) -> float:
    return n ** 3 / 3.0


def trsm_pair(n: int, k: int) -> float:
    return 2.0 * n * n * k


def solve_flops(op: str, n: int, nrhs: int) -> float:
    """One ``gesv`` (getrf + getrs) or ``posv`` (potrf + potrs)."""
    factor = {"gesv": getrf, "posv": potrf}[op]
    return factor(n) + trsm_pair(n, nrhs)
