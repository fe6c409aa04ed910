"""Operations of the Phase-2 steps (student forward and backward, teacher
and buffer forwards; no recompute) over the step's device time
(jit_p2_step) times the chip's bf16 peak."""

from bench import readers


def read(facts):
    return readers.mfu(facts, "step_flops", "step_device_s", "step_runs")
