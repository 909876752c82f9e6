"""95th percentile of the host time of one ``Session.step`` in the traced
window, in ms (harness clock around each call; inclusive quantiles, so a
window of a few steps reads near its slowest step)."""
import statistics


def read(facts):
    steps = facts.get("step_s") or []
    if len(steps) < 2:
        return None
    return statistics.quantiles(steps, n=20, method="inclusive")[18] * 1e3
