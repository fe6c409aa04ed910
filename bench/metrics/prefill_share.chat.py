"""Device time of the admission executables (jit_admit) over the device's
busy time in the traced window."""

from bench import readers


def read(facts):
    return readers.busy_share(facts, "admit_device_s")
