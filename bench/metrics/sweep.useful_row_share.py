"""Share of the rows the MLi-GD sweep solves that are real, in %: the
``rows`` stat of the program's ``mcsa.planner.solve_prep`` spans over their
``bucket`` stat (the pow2 size the rows are padded to), summed over the
traced window."""
from bench.program_spans import of, program_spans, stat


def read(facts):
    tr = program_spans(facts)
    if tr is None:
        return None
    hits = of(tr, "planner.solve_prep")
    rows, bucket = sum(stat(hits, "rows")), sum(stat(hits, "bucket"))
    if not bucket:
        return None
    return 100.0 * rows / bucket
