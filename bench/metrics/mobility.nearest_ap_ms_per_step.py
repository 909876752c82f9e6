"""Host time of the nearest-AP search per control step, in ms: the
program's span ``mcsa.mobility.nearest_ap`` (``topo.nearest_ap`` over every
user and the AP-to-server gather) summed over the traced window and
divided by its ``mcsa.session.step`` spans."""
from bench.program_spans import per_step_ms


def read(facts):
    return per_step_ms(facts, "mobility.nearest_ap")
