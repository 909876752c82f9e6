"""Run a cell on the CPU at a test size, with faults planted under the
timed path."""
import argparse
import json

from bench import run

SEED = 2**31 + 4099


def small_fleet(n=3000):
    def override(cfg):
        cfg = json.loads(json.dumps(cfg))
        cfg["fleet"]["num_users"] = n
        return cfg
    return override


def run_small(workload, hooks=None, seconds=3.0, config_override=None,
              traffic_override=None, seed=SEED):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=0)
    return run.run_cell(args, require_chip=False,
                        config_override=config_override or small_fleet(),
                        traffic_override=traffic_override, hooks=hooks)
