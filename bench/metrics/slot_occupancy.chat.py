"""Active rows over slot-ticks of the window's decode ticks, from the
engine's own counters read at the window's edges: delta decode_rows /
(delta decode_ticks x slots)."""

from bench import program_trace


def read(facts):
    c = facts.get("window_counters")
    return program_trace.slot_occupancy(*c, facts["slots"]) if c else None
