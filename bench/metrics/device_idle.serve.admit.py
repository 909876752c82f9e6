"""Share of the traced window in which the device ran nothing while the
host was admitting requests, in %: device idle time inside the program's
``mcsa.engine.admit`` spans over the window (a part of
``device_idle.serve``)."""
from bench.program_spans import idle_share


def read(facts):
    return idle_share(facts, "engine.admit")
