"""The one traffic generator: a mix's data file plus a seed -> the work.

The dense mixes are a closed loop (``"loop": "closed"``): one caller
that dispatches the next solve when the last answer is back.  Solve
``i`` factors ``A + s_i I`` with ``s_i`` drawn uniformly from the mix's
``shift`` range by ``(seed, i)``: every solve sees a matrix it has not
seen, and every seed does the same amount of work.
"""

from __future__ import annotations

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), *stream])


def shift(mix: dict, seed: int, i: int) -> float:
    lo, hi = mix["shift"]
    return float(lo + (hi - lo) * _rng(seed, 1, i).random())
