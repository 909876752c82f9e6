"""Device time of one prefill, in ms: the engine's jitted prefill program
(``jit__prefill`` in the trace's XLA Modules line), summed over the
window and divided by its launches (one per admission)."""
from bench.trace import program_time


def read(facts):
    hit = program_time(facts["trace"], "jit__prefill")
    if hit is None or not hit[1]:
        return None
    return hit[0] / hit[1] * 1e3
