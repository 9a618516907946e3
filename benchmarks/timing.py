"""Timing primitives for device benchmarks.

Dispatch returns before the device finishes and a scalar fetch pays a
host round trip, so the canonical method — same as the repo-root bench.py — chains K iterations of
the op inside one jitted program ending in a scalar fetch and takes the
slope between a small-K and a large-K run: fixed costs (dispatch, fetch,
compile cache hits) cancel, leaving seconds/op.

This is the TPU analog of the reference's chained-async benchmark loop
(test/host/test.py:923-1156: queue niter chained calls, wall-clock the
chain, divide).
"""

from __future__ import annotations

import time

import numpy as np


def timed_scalar(fn, args, reps: int = 5) -> float:
    """Min-of-reps wall time of fn(*args) forced to a host scalar.

    Min (not median): every timing includes the same device work plus a
    nonnegative noise term from the host scheduler, so the minimum is
    the tightest unbiased estimate of the true cost — medians still
    carry half the noise distribution."""
    float(fn(*args))  # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.min(ts))


def slope_time(make_chain, args, k_lo: int = 4, k_hi: int = 36,
               reps: int = 5) -> float:
    """Seconds per iteration via a least-squares slope over 3 K points.

    ``make_chain(K)`` must return a jitted callable running K chained
    iterations of the op and reducing to a scalar. Three points (lo, mid,
    hi) with min-of-reps timings give a slope robust to a single noisy
    measurement, which a 2-point difference is not.
    """
    k_mid = (k_lo + k_hi) // 2
    ks = np.array([k_lo, k_mid, k_hi], dtype=np.float64)
    ts = np.array([timed_scalar(make_chain(int(k)), args, reps=reps)
                   for k in ks])
    slope = float(np.polyfit(ks, ts, 1)[0])
    if slope <= 0:
        import warnings
        warnings.warn(
            f"non-positive timing slope (t={ts}): host too noisy or op too "
            f"small for K={k_lo}..{k_hi}; result clamped and unreliable",
            RuntimeWarning, stacklevel=2)
    return max(slope, 1e-9)


def wall_time(fn, reps: int = 20, warmup: int = 3) -> tuple[float, float]:
    """(p50, std) wall-clock seconds of a blocking host-side call — the
    emulator-tier method (no async dispatch to cancel out)."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), float(np.std(ts))
