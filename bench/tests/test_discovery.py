"""A new cell, traffic mix and per-layer metric are found by name from
files and entries alone: here they live in a temporary copy."""
import json
import os
import shutil

from bench import run


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
