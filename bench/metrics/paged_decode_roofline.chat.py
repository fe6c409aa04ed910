"""The paged_decode kernel's least time (the larger of its operations over
the peak and the K/V pages its rows' positions need over the HBM
bandwidth) over its device time."""

from bench import readers


def read(facts):
    return readers.roofline(facts, "paged_decode_least_s", "paged_decode_s")
