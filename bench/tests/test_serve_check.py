"""The serving cell's check at a tiny width on the CPU: a sound run
passes; the control (the reference with int8 weight matmuls, judged in
the program's place), a token altered where it is produced, a decode
that returns its cache unchanged, and a decode that leaves half of the
slots out, fail."""
import pytest

from helpers import run_small, tiny_config, tiny_traffic

CELL = "serve.starcoder2-3b.alpaca"


@pytest.fixture
def tiny():
    return tiny_overrides()


def tiny_overrides():
    """The serve cell at a tiny width (its cuts listed in the
    configuration's ``reduced``): overrides for ``run_small``."""
    def config(c):
        c = tiny_config(c)
        # the limit at this width, from its own readings on the CPU over
        # three seeds: sound runs 0.0004-0.0019, the control 0.023-0.034
        c["check"] = {"logit_gap_p99": 0.008}
        return c
    return {"config_override": config, "traffic_override": tiny_traffic}


def test_sound_run_is_correct(tiny):
    res = run_small(CELL, seconds=4.0, **tiny)
    assert res["correct"], res["checks"]
    assert res["checks"]["tokens_checked"]["value"] > 0
    # every program the window runs was built in set-up
    wc = res["window_compiles"]
    assert (wc["compiles"], wc["cache_loads"]) == (0, 0), wc


def test_control_fails(tiny):
    res = run_small(CELL, seconds=4.0, hooks={"control": True}, **tiny)
    assert not res["correct"], res["checks"]
    # the program's own numbers, read in the same run, pass
    prog = res["program_checks"]
    assert all(c["ok"] for c in prog.values()), prog


def _token_altered(engine):
    real, V = engine._decode_fn, engine.cfg.vocab_size

    def bad(params, token, pos, caches):
        logits, nxt, caches = real(params, token, pos, caches)
        return logits, (nxt + 1) % V, caches
    engine._decode_fn = bad


def _cache_unchanged(engine):
    real = engine._decode_fn

    def stale(params, token, pos, caches):
        logits, nxt, _ = real(params, token, pos, caches)
        return logits, nxt, caches
    engine._decode_fn = stale


def _half_batch(engine):
    """Half of the slots left out of the decode: they echo their input
    token in place of the one the model computes."""
    import jax.numpy as jnp
    real = engine._decode_fn

    def half(params, token, pos, caches):
        logits, nxt, caches = real(params, token, pos, caches)
        keep = jnp.arange(nxt.shape[0]) < nxt.shape[0] // 2
        return logits, jnp.where(keep, nxt, token[:, 0]), caches
    engine._decode_fn = half


@pytest.mark.parametrize("fault", [_token_altered, _cache_unchanged,
                                   _half_batch])
def test_fault_fails(tiny, fault):
    res = run_small(CELL, seconds=4.0, hooks={"engine": fault}, **tiny)
    assert not res["correct"], res["checks"]
