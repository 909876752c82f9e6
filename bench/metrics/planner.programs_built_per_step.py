"""Programs JAX builds (compiles, or loads from the persistent cache) for
one control step that meets handoff counts it has not seen, averaged
over the copy of the session that set-up walks ahead of the window
(JAX's own compile events).  The planner's eager operations on arrays
sized by each step's handoff count build a program per new count; a step
whose shapes are bucketed builds none."""


def read(facts):
    walk = facts.get("walk")
    if not walk or not walk["steps"]:
        return None
    return walk["programs"] / walk["steps"]
