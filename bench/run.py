#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``;
its configuration in the file that entry names; its traffic mix in
``bench/traffic/<traffic>.json``, whose ``kind`` names its generator
``bench/generators/<kind>.py``; each per-layer metric in
``bench/metrics/<metric>.py``; a serve configuration's plain reference in
``bench/reference/<module>.py``, named by its ``model.reference``, which
supplies the architecture's forward pass, weight-draw rule and operation
counts (the generator loads it through ``ctx["load_reference"]``, the
readers find it in ``facts["reference"]``).  A new cell, configuration,
traffic mix, metric or serving architecture is added as files and entries
alone.

The generator builds the system under test, warms every shape the cell
uses (set-up), runs the measured window, then checks what the window
produced against the plain reference (``bench/reference``).  With
``--trace 1`` the window runs under the JAX profiler and the result holds
the cell's per-layer metrics; with ``--trace 0`` its end-to-end metrics.

The run exits non-zero and prints no result when JAX finds no TPU, or
fewer chips than the cell asks for.  The last lines on standard error,
and the ``checks`` key that ends the result line, give every number the
correctness check compared, with its limit.
"""
from __future__ import annotations

import argparse
import ctypes
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if sys.path and os.path.abspath(sys.path[0] or ".") == BENCH:
    # run as a script: import the harness as the package ``bench`` (its
    # ``trace`` module must not shadow the standard library's)
    sys.path[0] = ROOT
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str, root: str = ROOT) -> dict:
    """The cell, its configuration file, its traffic mix, and the metrics
    it reports (``end_to_end`` and ``per_layer``, each filtered by its
    ``workloads`` key where it has one), found by name under ``root``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and m["moves"] in reported]
    return {"workload": w, "config": cfg, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer}


def generator_for(traffic: dict, bench_dir: str = BENCH):
    kind = traffic["kind"]
    return load_module(os.path.join(bench_dir, "generators", kind + ".py"),
                       f"bench_generator_{kind}")


def read_metric(name: str, facts: dict, bench_dir: str = BENCH):
    """Value of per-layer metric ``name`` from the run's facts, or None
    where its reader finds nothing to read."""
    mod = load_module(os.path.join(bench_dir, "metrics", name + ".py"),
                      "bench_metric_" + name.replace(".", "_"))
    return mod.read(facts)


class CompileCounter:
    """Counts the programs JAX builds, in all and inside the measured
    window: those compiled, and those loaded from the persistent cache;
    and the Python garbage collections inside the window, with their time.
    Set-up caches every program it compiles; the window runs with JAX's
    default threshold (programs that compile in under a second are not
    written to the cache), as the program runs for its users."""

    def __init__(self):
        self.built = 0          # compiled or loaded from the cache
        self.cache_hits = 0
        self.window = None
        self.in_window = False
        self.names = []         # programs built inside the window
        self.gc = [0, 0.0]      # collections and their seconds, in window
        self._gc_t0 = None

    @property
    def compiles(self) -> int:
        return self.built - self.cache_hits

    def begin_window(self):
        import jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        self.window = (self.compiles, self.cache_hits)
        self.in_window = True
        gc.callbacks.append(self._on_gc)

    def end_window(self):
        self.in_window = False
        gc.callbacks.remove(self._on_gc)
        c, h = self.window
        self.window = (self.compiles - c, self.cache_hits - h)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc[0] += 1
            self.gc[1] += time.perf_counter() - self._gc_t0

    def install(self):
        from jax import monitoring

        def on_duration(event, duration, fun_name="?", **_):
            if event.endswith("backend_compile_duration"):
                self.built += 1
                if self.in_window:
                    self.names.append(fun_name)

        def on_event(event, **_):
            if event.endswith("compilation_cache/cache_hits"):
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


def use_cache() -> None:
    """JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache``, every program cached; the program's own helper is
    handed the same directory."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no size limit, so no eviction: entries are only ever added
    jax.config.update("jax_compilation_cache_max_size", -1)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()


def device_info(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(
            f"this cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s) "
            f"({devs[0].device_kind})")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def run_cell(args, *, require_chip: bool = True, config_override=None,
             traffic_override=None, hooks=None, root: str = ROOT) -> dict:
    """Set up, measure and check one cell; returns the result dict (the
    result line without printing).  ``config_override``, ``hooks`` and
    ``root`` serve the tests: a smaller configuration, faults planted
    under the timed path, and a copy of the benchmark's files to find the
    cell in (tests only)."""
    control = bool((hooks or {}).get("control"))
    bench_dir = os.path.join(root, "bench")
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = find_cell(bench, args.workload, root)
    if config_override is not None:
        cell["config"] = config_override(cell["config"])
    if traffic_override is not None:
        cell["traffic"] = traffic_override(cell["traffic"])
    if os.path.join(ROOT, "src") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))
    use_cache()
    device = device_info(cell["workload"]["chips"], require_chip)
    counter = CompileCounter()
    counter.install()
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(TRACE_DIR, args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = {
        "workload": cell["workload"], "config": cell["config"],
        "traffic": cell["traffic"], "seed": int(args.seed),
        "seconds": float(args.seconds), "trace_dir": trace_dir,
        "compiles": counter, "setup_clock": process_age_s,
        "memory_peak_bytes": memory_peak_bytes, "hooks": hooks or {},
        "log": lambda msg: print(msg, file=sys.stderr, flush=True),
        "load_reference": lambda name: load_module(
            os.path.join(bench_dir, "reference", name + ".py"),
            "bench_reference_" + name),
    }
    out = generator_for(cell["traffic"], bench_dir).run(ctx)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    facts = out["facts"]
    facts.update(device=device, config=cell["config"],
                 traffic=cell["traffic"])
    if args.trace:
        from bench.trace import find_xplane, reduce_trace
        red = reduce_trace(find_xplane(trace_dir))
        facts["trace"] = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        metrics = {}
        for m in cell["per_layer"]:
            v = read_metric(m["name"], facts, bench_dir)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = {m["name"]: {"value": out["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    # a control run is judged by the control's numbers, which stand in
    # the program's place; the program's own are kept beside them
    judged = out["control"] if control else out["checks"]
    result = {"correct": bool(judged) and all(c["ok"] for c in judged),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]),
              "metrics": metrics, "device": device}
    if args.trace:
        red = facts["trace"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    if control:
        result["program_checks"] = {
            c["name"]: {"value": c["value"], "limit": c["limit"],
                        "ok": c["ok"]} for c in out["checks"]}
    if "readings" in out:
        result["readings"] = out["readings"]
    ctx["log"](f"window gc: {counter.gc[0]} collections, "
               f"{counter.gc[1]:.4f} s")
    result["window_compiles"] = {"compiles": counter.window[0],
                                 "cache_loads": counter.window[1],
                                 "programs": sorted(set(counter.names))}
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in judged}
    return result


def pin_allocator() -> None:
    """Fix glibc's mmap and trim thresholds at the ceilings its own dynamic
    adjustment can reach (32 MiB, and twice that for trimming).  Left
    dynamic, they end wherever the largest freed block of the process
    put them: higher in a process that compiled its programs than in one
    that loaded them from the cache, which made the host's numpy
    page-fault more, and the control step ~9 % slower, in every run but
    the first of a checkout."""
    try:
        libc = ctypes.CDLL("libc.so.6")
    except OSError:
        return
    M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3
    libc.mallopt(M_MMAP_THRESHOLD, 32 << 20)
    libc.mallopt(M_TRIM_THRESHOLD, 64 << 20)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the control in the program's place (the "
                    "reference one precision lower): `correct` then comes "
                    "from the control's numbers, which have to fail their "
                    "limits; not part of a benchmark run")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    pin_allocator()
    result = run_cell(args, hooks={"control": True} if args.control else None)
    wc = result["window_compiles"]
    print(f"window_compiles compiles={wc['compiles']} "
          f"cache_loads={wc['cache_loads']} "
          f"programs={wc['programs'][:20]}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
