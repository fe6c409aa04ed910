"""The fused kd_loss kernels' least time (forward: read three f32 logit
tensors; backward: read three, write one) over their device time."""

from bench import readers


def read(facts):
    return readers.roofline(facts, "kd_loss_least_s", "kd_loss_s")
