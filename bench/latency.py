"""Commit latency from the window's per-wave verdicts.

A transaction's latency runs from the start of the wave of its first
attempt to the end of the wave in which it commits, counted in waves and
timed at the window's mean wave time: the window's seconds over its waves.
The device runs the chunks back to back, so the host's view of when one
chunk ended carries its own wake-up delay (a late view of one chunk makes
the next look short by as much); only the window as a whole is timed
well by the host's clock.  Waves before the window, where retried
transactions began, take the same wave time.  Transactions still
uncommitted when the window ends enter at the age they have reached, so
starvation cannot hide.
"""
from __future__ import annotations

import numpy as np


def latencies(commit: np.ndarray, age: np.ndarray,
              window_s: float) -> np.ndarray:
    """Seconds per transaction.

    ``commit``/``age``: [waves, lanes] for every wave of the window, in
    order; ``age`` is the number of earlier attempts of the lane's
    transaction."""
    n_waves = commit.shape[0]
    wave_s = window_s / n_waves
    wave, lane = np.nonzero(commit)
    done = age[wave, lane] + 1
    last = n_waves - 1
    pending = age[last, ~commit[last]] + 1
    return np.concatenate([done, pending]).astype(float) * wave_s


def p95_ms(lat_s: np.ndarray) -> float:
    return float(np.percentile(lat_s, 95) * 1e3)
