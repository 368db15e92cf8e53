"""Arithmetic the per-layer metric readers share.

A reader that finds nothing to read returns None, and the harness leaves
its metric out of the line; no share of a peak is ever reported as 0.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple


def idle_pct(summary) -> Optional[float]:
    if summary is None or summary.window_s <= 0:
        return None
    return 100.0 * (1.0 - summary.busy_s / summary.window_s)


def module(summary, name_part: str = None, count: int = None
           ) -> Optional[Tuple[int, float]]:
    """(executions, device seconds) of one jitted program: the one whose
    name holds ``name_part``, else the one whose executions are nearest
    ``count``."""
    mods = summary.modules if summary else {}
    if name_part is not None:
        hits = [v for n, v in mods.items() if name_part in n]
        return max(hits, key=lambda v: v[1]) if hits else None
    if count:
        return min(mods.values(), key=lambda v: (abs(v[0] - count), -v[1]),
                   default=None)
    return None


def share(work: float, seconds: float, peak: float) -> Optional[float]:
    if not seconds or seconds <= 0 or not math.isfinite(peak) or work <= 0:
        return None
    return 100.0 * work / seconds / peak
