"""Device time of one decode step, in ms: the engine's jitted decode
program (``jit__decode``), summed over the window and divided by its
launches (one per engine step with an active slot)."""
from bench.trace import program_time


def read(facts):
    hit = program_time(facts["trace"], "jit__decode")
    if hit is None or not hit[1]:
        return None
    return hit[0] / hit[1] * 1e3
