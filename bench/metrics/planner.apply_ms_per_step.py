"""Host time spent applying the previous asynchronous replan per control
step, in ms: the program's span ``mcsa.planner.apply`` (forcing the
dispatched solve and scattering its rows into the plan table) summed over
the traced window and divided by its ``mcsa.session.step`` spans."""
from bench.program_spans import per_step_ms


def read(facts):
    return per_step_ms(facts, "planner.apply")
