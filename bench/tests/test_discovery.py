"""A new cell, traffic mix and per-layer metric are found by name from
files and entries alone: here they live in a temporary copy."""
import json
import os
import shutil

from bench import run
from helpers import run_small, tiny_config, tiny_traffic


def test_new_cell_and_metric_from_files(tmp_path):
    root = tmp_path
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(run.ROOT, "bench", sub),
                        root / "bench" / sub)
    (root / "bench" / "traffic" / "jog.json").write_text(json.dumps(
        {"kind": "mobility", "speed_range": [2.0, 4.0], "dt": 30.0,
         "mobility_seed": 7, "probe_steps": 2,
         "ahead_margin": 1.25, "check_share": 0.5, "check_steps": 1,
         "check_rows": 64}))
    (root / "bench" / "metrics" / "mobility.calls.py").write_text(
        "def read(facts):\n"
        "    return len(facts.get('mobility_s') or []) or None\n")
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    bench["workloads"].append({"name": "plan.nin.jog", "config": "nin",
                               "traffic": "jog", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "mobility.calls", "unit": "calls", "better": "lower",
        "source": "host_clock", "layer": "mobility (core/mobility.py)",
        "moves": "control_step_ms", "workloads": ["plan.nin.jog"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("control_step_ms", "mobility.ms_per_step"):
            m["workloads"].append("plan.nin.jog")

    cell = run.find_cell(bench, "plan.nin.jog", root=str(root))
    assert cell["traffic"]["speed_range"] == [2.0, 4.0]
    assert cell["config"]["name"] == "nin"
    names = [m["name"] for m in cell["per_layer"]]
    assert "mobility.calls" in names and "mobility.ms_per_step" in names
    assert [m["name"] for m in cell["end_to_end"]] == ["control_step_ms",
                                                        "setup_s"]
    assert run.generator_for(cell["traffic"]).__name__.endswith("mobility")
    facts = {"mobility_s": [0.1, 0.2, 0.3]}
    assert run.read_metric("mobility.calls", facts,
                           bench_dir=str(root / "bench")) == 3
    # the metric is not reported where its workloads key leaves it out
    other = run.find_cell(bench, "plan.nin.vehicular", root=str(root))
    assert "mobility.calls" not in [m["name"] for m in other["per_layer"]]


# a reference module of a new architecture, here the dense equations
# handed on, which notes each part the harness takes from it
NEW_REFERENCE = '''import os

from bench.reference import lm

LOG = os.path.join(os.path.dirname(__file__), "calls.txt")


def _note(part):
    with open(LOG, "a") as f:
        f.write(part + "\\n")


def weight_rule(m):
    _note("weight_rule")
    return lm.weight_rule(m)


def forward(m, params, tokens, int8=False):
    _note("forward")
    return lm.forward(m, params, tokens, int8)


decode_step_work = lm.decode_step_work
prefill_flops = lm.prefill_flops
'''


def test_new_serve_architecture_from_files(tmp_path):
    """A serve configuration whose ``reference`` names a new module file,
    with its traffic and cell, all in a copy: the run draws the weights
    by that module's rule and checks the window against its forward."""
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = tmp_path / "bench"
    cfg = tiny_config(run.load_json(str(b / "configs" / "starcoder2-3b.json")))
    cfg["name"] = "tiny-dense"
    cfg["model"]["reference"] = "tiny_dense"
    cfg["check"] = {"logit_gap_p99": 0.008}
    (b / "configs" / "tiny-dense.json").write_text(json.dumps(cfg))
    (b / "reference" / "tiny_dense.py").write_text(NEW_REFERENCE)
    (b / "traffic" / "tiny.json").write_text(json.dumps(tiny_traffic(
        run.load_json(str(b / "traffic" / "alpaca.json")))))
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell = "serve.tiny-dense.tiny"
    bench["configs"].append({
        "name": "tiny-dense", "source": "test",
        "file": "bench/configs/tiny-dense.json",
        "reduced": cfg["reduced"], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "tiny-dense",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("output_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"):
            m["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    res = run_small(cell, seconds=3.0, config_override=lambda c: c,
                    root=str(tmp_path))
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"output_tokens_per_s", "ttft_p95_ms",
                                   "itl_p95_ms", "setup_s"}
    calls = (b / "reference" / "calls.txt").read_text().split()
    assert {"weight_rule", "forward"} <= set(calls)
