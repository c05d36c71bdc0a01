"""Helpers the metric readers share: what a run's window holds."""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def spans(run: dict, name: str) -> List[float]:
    """Durations (s) of the host spans ``name`` that began in the window."""
    return [b - a for n, a, b in run["record"].spans
            if n == name and run["t0"] <= a <= run["t1"]]


def occupancy(run: dict) -> Optional[float]:
    """Mean formed chunk over batch size, over the window's batches."""
    b = [c / s for t, c, s in run["record"].batches
         if run["t0"] <= t <= run["t1"]]
    return float(np.mean(b)) if b else None


def idle_share(run: dict) -> Optional[float]:
    red = run["trace"]
    if red is None or not red["devices"] or red["window_s"] <= 0:
        return None
    return 1.0 - red["busy_s"] / red["window_s"]
