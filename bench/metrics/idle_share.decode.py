"""1 - (union of the device's op intervals) / traced window, in %."""

from bench import readers


def read(facts):
    return readers.idle_share(facts)
