"""Host time of the eager work before the replan's dispatch per control
step, in ms: the program's span ``mcsa.planner.solve_prep`` (candidate
gathers, casts, rent and padding to the pow2 bucket, the first block of
``MCSAPlanner._solve_dirty``) summed over the traced window and divided by
its ``mcsa.session.step`` spans."""
from bench.program_spans import per_step_ms


def read(facts):
    return per_step_ms(facts, "planner.solve_prep")
