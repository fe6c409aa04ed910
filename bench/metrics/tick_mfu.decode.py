"""Model operations of the decode ticks (active rows only) over the ticks'
device time (jit_tick) times the chip's bf16 peak."""

from bench import readers


def read(facts):
    return readers.mfu(facts, "tick_flops", "tick_device_s")
