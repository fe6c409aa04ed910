"""Arithmetic shared by the per-layer metric readers (``bench/metrics``).

A reader takes the facts a driver gathered and returns a number, or None
where the run gave it nothing to read (no trace, no such work in the
window).  A share of a peak or of a roofline is never reported as 0 for
want of data.
"""

from __future__ import annotations

import numpy as np


def ratio(facts, num, den):
    """``facts[num] / facts[den]`` in %, or None."""
    n, d = facts.get(num), facts.get(den)
    if n is None or not d:
        return None
    return 100.0 * n / d


def idle_share(facts):
    dev = facts.get("trace_device")
    if not dev or not dev["window_s"]:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])


def busy_share(facts, key):
    """Share of the device's busy time spent in ``facts[key]`` seconds."""
    dev = facts.get("trace_device")
    if not dev or not dev["busy_s"] or not facts.get(key):
        return None
    return 100.0 * facts[key] / dev["busy_s"]


def mfu(facts, flops_key, seconds_key, runs_key=None):
    """Model operations over (device seconds x peak), in %."""
    secs, flops = facts.get(seconds_key), facts.get(flops_key)
    if not secs or not flops:
        return None
    runs = facts[runs_key] if runs_key else 1
    return 100.0 * flops * runs / (secs * facts["peak_flops"])


def roofline(facts, least_key, seconds_key):
    """The kernel's least time over its measured time, in %."""
    return ratio(facts, least_key, seconds_key)


def percentile(xs, q):
    """The ``q``-th percentile of ``xs``, or None where ``xs`` is empty."""
    if not len(xs):
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))
