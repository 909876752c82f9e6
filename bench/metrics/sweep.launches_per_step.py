"""Launches of the fused Li-GD/MLi-GD sweep kernels per control step: the
count of the trace's ``mcsa_ligd_sweep`` and ``mcsa_mligd_sweep`` events
in the window over the steps (one a step where every step replans)."""
from bench.trace import op_time

KERNELS = r"mcsa_m?ligd_sweep"


def read(facts):
    hit = op_time(facts["trace"], KERNELS)
    if hit is None or not facts.get("steps"):
        return None
    return hit[1] / facts["steps"]
