"""Poisson arrivals: exponential gaps at ``rate_per_s``.

The gaps are the exponential distribution's quantiles at the midpoints of
``n`` equal steps, so every seed gets the same set of gaps; the seed only
orders them.
"""
import numpy as np


def gaps(traffic, n):
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / float(traffic["rate_per_s"])
