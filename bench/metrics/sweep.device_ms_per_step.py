"""Device time of the fused Li-GD/MLi-GD sweep kernels per control step,
in ms: the summed durations of the trace's ``mcsa_ligd_sweep`` and
``mcsa_mligd_sweep`` events in the window."""
from bench.trace import op_time

KERNELS = r"mcsa_m?ligd_sweep"


def read(facts):
    hit = op_time(facts["trace"], KERNELS)
    if hit is None or not facts.get("steps"):
        return None
    return hit[0] / facts["steps"] * 1e3
