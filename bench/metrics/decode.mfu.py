"""Model FLOP/s utilization of the whole engine step, in %: the useful
operations of every ``InferenceEngine.step`` in the traced window (the
decode of the active slots with attention over their filled positions,
and the prefill of the prompts admitted, as the configuration's reference
module counts them) over the summed host-clock time of those calls times
the chip's peak bf16 FLOP/s."""
from bench import flops


def read(facts):
    info, times = facts.get("step_info") or [], facts.get("step_s") or []
    if not info or len(info) != len(times) or not sum(times):
        return None
    m, ref = facts["config"]["model"], facts["reference"]
    peak = flops.peaks(facts["device"]["kind"])["bf16_flops_per_s"]
    useful = 0.0
    for _active, positions, admitted in info:
        useful += ref.decode_step_work(
            m, list(positions) + list(admitted))["flops"]
        useful += sum(ref.prefill_flops(m, s) for s in admitted)
    return 100.0 * useful / (sum(times) * peak)
