"""Run a cell on the CPU at a test size, with faults planted under the
timed path."""
import argparse
import json

from bench import run

SEED = 2**31 + 4099

# the serve configuration at a tiny width, cut from the registered
# StarCoder2-3B through the configuration's ``reduced`` list
TINY = {"num_layers": 2, "d_model": 256, "num_heads": 4, "num_kv_heads": 2,
        "head_dim": 64, "d_ff": 512, "vocab_size": 2048}


def tiny_config(cfg):
    """A copy of a serve configuration at the tiny width, with a small
    engine."""
    cfg = json.loads(json.dumps(cfg))
    cfg["model"].update(TINY)
    cfg["reduced"] = sorted(TINY)
    cfg["engine"] = {"slots": 4, "cache_len": 96}
    return cfg


def tiny_traffic(t):
    """An open-loop mix whose requests fit the tiny engine."""
    return dict(t, rate_per_s=20.0, first_token_wait_s=20.0,
                prompt_len={"median": 8, "sigma": 0.5, "min": 4, "max": 12},
                output_len={"median": 20, "sigma": 0.8, "min": 4, "max": 80})


def small_fleet(n=3000):
    def override(cfg):
        cfg = json.loads(json.dumps(cfg))
        cfg["fleet"]["num_users"] = n
        return cfg
    return override


def run_small(workload, hooks=None, seconds=3.0, config_override=None,
              traffic_override=None, seed=SEED, root=run.ROOT):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=0)
    return run.run_cell(args, require_chip=False,
                        config_override=config_override or small_fleet(),
                        traffic_override=traffic_override, hooks=hooks,
                        root=root)
