"""The serve cell draws and judges exactly as it did before the
architecture moved into the reference module: at the tiny width and one
seed, the sum of every weight leaf, and the numbers the check reads over
one hand-made sample, equal to the last digit what the harness gave at
commit 21ec06d (its ``make_weights(mcfg, seed)`` and
``compare(cfg, params, sample, hooks, block)``)."""
import json
import os

import jax
import numpy as np
import pytest

from bench import run
from bench.generators import open_loop
from bench.reference import lm
from helpers import SEED, tiny_config

SUMS = {
    "['embed']": -12.998161882162094,
    "['final_norm']": -0.5562257766723633,
    "['stack']['scan'][0]['ffn']['wd']": -3.4032405130565166,
    "['stack']['scan'][0]['ffn']['wg']": -18.27168575208634,
    "['stack']['scan'][0]['ffn']['wu']": -34.00871664099395,
    "['stack']['scan'][0]['ln1']": 3.6716699600219727,
    "['stack']['scan'][0]['ln2']": 2.164240837097168,
    "['stack']['scan'][0]['mix']['wk']": -0.6542057730257511,
    "['stack']['scan'][0]['mix']['wo']": 19.99561574193649,
    "['stack']['scan'][0]['mix']['wq']": -15.639479894191027,
    "['stack']['scan'][0]['mix']['wv']": -1.0800011195242405,
    "['unembed']": 41.18133509438485,
}
READINGS = {
    "program": {"logit_gap_max": 6.163913249969482,
                "logit_gap_p99": 5.837471961975098,
                "token_mismatch_share": 0.9922480620155039},
    "control": {"logit_gap_max": 0.02919531799852848,
                "logit_gap_p99": 0.018308918923139572,
                "token_mismatch_share": 0.031007751937984496},
}


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config(run.load_json(os.path.join(
        run.BENCH, "configs", "starcoder2-3b.json")))
    mcfg = open_loop.program_config(cfg)
    params = open_loop.make_weights(mcfg, SEED,
                                    lm.weight_rule(cfg["model"]))
    return cfg, params


def test_weight_sums(tiny):
    _, params = tiny
    sums = {jax.tree_util.keystr(k): float(
                np.asarray(v, np.float32).sum(dtype=np.float64))
            for k, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert sums == SUMS


def test_check_readings(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(17)
    sample = [(rng.integers(0, 2048, a).astype(np.int32),
               rng.integers(0, 2048, b).astype(np.int32))
              for a, b in ((5, 40), (12, 9), (7, 80))]
    _, _, readings = open_loop.compare(cfg, params, sample,
                                       {"control": True}, 2, lm.forward)
    assert json.loads(json.dumps(readings)) == READINGS
