"""Share of the roofline reached by the decode steps, in %: the least
time the chip needs for the traced window's decode steps (for each, the
larger of its operations over peak FLOP/s and its bytes over HBM
bandwidth, as the configuration's reference module counts them: for the
dense ``lm``, every weight once and the cache of the positions each
active slot attends over, ``bench/flops.py``) over the device time of the
decode program.  The bound is memory at these shapes."""
from bench import flops
from bench.trace import program_time


def read(facts):
    hit = program_time(facts["trace"], "jit__decode")
    info = facts.get("step_info") or []
    if hit is None or not hit[0] or not info:
        return None
    m, ref = facts["config"]["model"], facts["reference"]
    peak = flops.peaks(facts["device"]["kind"])
    need = 0.0
    for _active, positions, admitted in info:
        work = ref.decode_step_work(m, list(positions) + list(admitted))
        need += flops.least_time_s(work, peak)[0]
    # steps of the window that ran without the trace's decode launches
    # (none do: every step with work decodes) scale the same way
    need *= hit[1] / len(info)
    return 100.0 * need / hit[0]
