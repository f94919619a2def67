"""Percentiles and the arithmetic of windows.

Kept here so that every PR computes a tail the same way. Percentiles use
linear interpolation between order statistics (numpy's default), are taken
over every sample of the window, and are never rounded.
"""

import numpy as np


def percentile(values, q):
    """q-th percentile (0-100) of ``values``; None when there are none."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def median(values):
    return percentile(values, 50)


def rate_in_window(stamps_s, counts, start_s, end_s):
    """Units per second delivered in [start, end): ``counts[i]`` units at
    time ``stamps_s[i]``."""
    total = sum(c for t, c in zip(stamps_s, counts) if start_s <= t < end_s)
    return total / (end_s - start_s)


def spread(values):
    """Distance between the quartiles over the median (the driver's
    measure of run-to-run spread)."""
    q1, q2, q3 = (percentile(values, q) for q in (25, 50, 75))
    return (q3 - q1) / q2
