"""Host time in the mobility model's ``step`` per control step, in ms
(harness span ``bench.mobility`` around ``session.mobility.step``)."""


def read(facts):
    t = facts.get("mobility_s")
    if not t or not facts.get("steps"):
        return None
    return sum(t) / facts["steps"] * 1e3
