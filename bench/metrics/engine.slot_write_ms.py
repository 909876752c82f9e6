"""Host time of one admission's cache write into its slot, in ms: the
program's span ``mcsa.engine.slot_write`` (the eager per-leaf write into
the slot pool and the last-token update), averaged over the admissions of
the traced window."""
from bench.program_spans import mean_ms


def read(facts):
    return mean_ms(facts, "engine.slot_write")
