"""Share of the traced window in which no operation ran on the device,
in % (1 - union of op intervals / window), planner cells."""


def read(facts):
    tr = facts["trace"]
    if tr["window_s"] <= 0 or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
