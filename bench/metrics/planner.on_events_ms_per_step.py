"""Host time in the planner's event pipeline per control step, in ms
(harness span ``bench.on_events`` around ``session.policy.on_events``:
dirty set, solve dispatch, apply of the previous replan, scatter)."""


def read(facts):
    t = facts.get("on_events_s")
    if not t or not facts.get("steps"):
        return None
    return sum(t) / facts["steps"] * 1e3
