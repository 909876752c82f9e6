"""The serve harness's seam between the configuration and the program:
``program_config`` holds the registered model to the configuration and
applies its ``reduced`` cuts; the reference module's weight rule names
every leaf and refuses what it does not cover; the readers count the work
by the configured module."""
import json
import os

import pytest

from bench import run
from bench.generators import open_loop
from bench.reference import lm
from helpers import SEED, TINY, tiny_config

SERVE = os.path.join(run.BENCH, "configs", "starcoder2-3b.json")


@pytest.fixture
def serve_cfg():
    return run.load_json(SERVE)


def test_published_config_is_the_registry(serve_cfg):
    from repro.configs import get_config
    assert open_loop.program_config(serve_cfg) == get_config("starcoder2-3b")


def test_reduced_keys_are_applied(serve_cfg):
    mcfg = open_loop.program_config(tiny_config(serve_cfg))
    for k, v in TINY.items():
        assert getattr(mcfg, k) == v
    assert mcfg.rope_theta == 100_000.0


def test_unlisted_departure_is_refused(serve_cfg):
    serve_cfg["model"]["num_layers"] = 4
    with pytest.raises(ValueError, match="num_layers"):
        open_loop.program_config(serve_cfg)


def test_cut_must_be_stated(serve_cfg):
    serve_cfg["reduced"] = ["num_experts"]
    with pytest.raises(ValueError, match="num_experts"):
        open_loop.program_config(serve_cfg)


def test_unstated_registry_field_is_refused():
    cfg = {"reduced": [], "model": {
        "kind": "transformer", "model_id": "granite-moe-1b-a400m",
        "reference": "lm", "num_layers": 24, "d_model": 1024,
        "num_heads": 16, "num_kv_heads": 8, "head_dim": 64, "d_ff": 512,
        "vocab_size": 49_155, "experts_per_token": 8,
        "rope_theta": 10_000.0, "tie_embeddings": True}}
    with pytest.raises(ValueError, match="num_experts"):
        open_loop.program_config(cfg)
    cfg["model"]["num_experts"] = 32
    open_loop.program_config(cfg)


@pytest.mark.parametrize("drop", ["model.reference", "reduced"])
def test_missing_key_is_named(serve_cfg, drop):
    group, _, key = drop.rpartition(".")
    del (serve_cfg[group] if group else serve_cfg)[key]
    with pytest.raises(ValueError, match=f"`{key}`"):
        open_loop.program_config(serve_cfg)


def test_unknown_leaf_is_an_error(serve_cfg):
    cfg = tiny_config(serve_cfg)
    rule = {k: v for k, v in lm.weight_rule(cfg["model"]).items()
            if k != "wq"}
    with pytest.raises(ValueError, match="wq"):
        open_loop.make_weights(open_loop.program_config(cfg), SEED, rule)


@pytest.mark.parametrize("extra", [
    {"num_experts": 32, "experts_per_token": 8},
    {"pattern": ["local", "global"]},
    {"tie_embeddings": True},
])
def test_lm_refuses_what_it_does_not_cover(serve_cfg, extra):
    with pytest.raises(ValueError, match="does not cover"):
        lm.weight_rule(dict(serve_cfg["model"], **extra))


def test_readers_count_by_the_configured_module(serve_cfg):
    class Counts:
        def decode_step_work(m, positions):
            return {"flops": 1e9 * len(positions), "bytes": 1.0}

        def prefill_flops(m, prompt_len):
            return 1e6 * prompt_len

    facts = {"config": serve_cfg, "reference": Counts,
             "device": {"kind": "TPU v5 lite"},
             "step_info": [(2, [5, 9], []), (3, [6, 10], [4])],
             "step_s": [0.5, 0.5]}
    peak = 197e12
    want = 100.0 * (2e9 + 3e9 + 4e6) / (1.0 * peak)
    got = run.read_metric("decode.mfu", facts)
    assert got == pytest.approx(want, rel=1e-12)


def test_benchmark_reduced_matches_each_file():
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    for c in bench["configs"]:
        with open(os.path.join(run.ROOT, c["file"])) as f:
            assert sorted(json.load(f)["reduced"]) == sorted(c["reduced"]), \
                c["name"]
