"""The yardstick's arithmetic for StarCoder2-3B as the program builds it,
against a count by hand."""
import json
import os

import pytest

from bench import flops

CFG = os.path.join(os.path.dirname(__file__), "..", "configs",
                   "starcoder2-3b.json")


@pytest.fixture
def model():
    with open(CFG) as f:
        return json.load(f)["model"]


def test_parameters_by_hand(model):
    d, V, ff, L = 3072, 49152, 12288, 30
    layer = d * 24 * 128 * 2 + d * 2 * 128 * 2 + 3 * d * ff + 2 * d
    assert flops.num_params(model) == L * layer + d + 2 * V * d \
        == 4_312_980_480


def test_kv_bytes_per_token(model):
    assert flops.kv_bytes_per_token(model) == 30 * 2 * 2 * 128 * 2 == 30_720


def test_decode_is_memory_bound_on_v5e(model):
    peak = flops.peaks("TPU v5 lite")
    work = flops.decode_step_work(model, [100] * 64)
    t, bound = flops.least_time_s(work, peak)
    assert bound == "memory"
    assert t == pytest.approx(
        (flops.weight_bytes(model) + 64 * 101 * 30_720) / 819e9)


def test_prefill_flops_grow_with_prompt(model):
    assert flops.prefill_flops(model, 64) > 2 * flops.prefill_flops(
        model, 31)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")
