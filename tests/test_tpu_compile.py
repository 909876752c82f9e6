"""Compile the main path's device programs for a described TPU v5e.

No chip is attached: the TPU compiler builds for a ``v5e:2x2`` topology
that is only described, so Mosaic and XLA refuse here what they would
refuse on the chip (unsupported vector layouts, too much VMEM, programs
that do not fit HBM).  Nothing runs, so no numbers come out of these.

The topology is described inside a module fixture (never at import):
only one process may hold libtpu, and every test worker imports this
file.  Keep all such compiles in this one file so one worker holds it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 2**30
USERS = 100_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def starcoder():
    from repro.configs import get_config
    return get_config("starcoder2-3b")


@pytest.fixture(scope="module")
def tables(starcoder):
    from repro.configs import get_config
    from repro.core.profile import profile_of
    from repro.kernels.ligd_step import sweep_tables
    return {"nin": sweep_tables(profile_of(get_config("nin"))),
            "starcoder2-3b": sweep_tables(
                profile_of(starcoder, seq=128, mode="prefill"))}


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("X", [USERS, 300])
@pytest.mark.parametrize("profile", ["nin", "starcoder2-3b"])
@pytest.mark.parametrize("joint", [False, True], ids=["ligd", "mligd"])
def test_sweep_kernel_compiles(one_chip, tables, profile, joint, X):
    """The fused sweep at the planner's defaults (chunk=1, user_block=
    2048), for a 10-split CNN and a 31-split transformer profile, at the
    megafleet width and at a ragged width below one block."""
    from repro.kernels.ligd_step import NF_SWEEP, sweep_tpu
    K = 4 if joint else 2
    init = (0.5,) * K
    fn = jax.jit(lambda f, x: sweep_tpu(
        f, x, tables=tables[profile], joint=joint, init=init, chunk=1,
        max_iters=60))
    compiled = fn.lower(_spec((NF_SWEEP, X), jnp.float32, one_chip),
                        _spec((K, X), jnp.float32, one_chip)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert ("mcsa_mligd_sweep" if joint else "mcsa_ligd_sweep") in hlo


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.fixture(scope="module")
def full_params(starcoder, one_chip):
    from repro.models import transformer as tfm
    from repro.runtime.meshenv import CPU_ENV
    shapes = jax.eval_shape(
        lambda k: tfm.init_lm(starcoder, k, CPU_ENV)[0],
        jax.random.PRNGKey(0))
    return jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), shapes)


@pytest.mark.parametrize("step", ["init_lm", "decode_step", "prefill"])
def test_full_width_engine_fits_one_chip(starcoder, full_params, one_chip,
                                         step):
    """starcoder2-3b at its published widths, as the edge engine builds
    and runs it (4 slots x 1024 cache; a 700-token prefill), fits one
    v5e's HBM; the weight init draws no whole f32 temporaries."""
    from repro.models import transformer as tfm
    from repro.runtime.meshenv import CPU_ENV
    cfg, slots, L = starcoder, 4, 1024
    if step == "init_lm":
        fn = jax.jit(lambda k: tfm.init_lm(cfg, k, CPU_ENV)[0])
        compiled = fn.lower(_spec((2,), jnp.uint32, one_chip)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 2**30
        assert 8 * 2**30 < _total_bytes(compiled) < HBM_BYTES
        return
    if step == "decode_step":
        caches = jax.eval_shape(
            lambda: tfm.init_caches(cfg, CPU_ENV, slots, L)[0])
        caches = jax.tree.map(
            lambda s: _spec(s.shape, s.dtype, one_chip), caches)
        fn = jax.jit(lambda p, tok, pos, c: tfm.decode_step(
            cfg, p, CPU_ENV, tok, pos, c))
        lowered = fn.lower(full_params,
                           _spec((slots, 1), jnp.int32, one_chip),
                           _spec((slots,), jnp.int32, one_chip), caches)
    else:
        fn = jax.jit(lambda p, tok: tfm.prefill(
            cfg, p, CPU_ENV, {"tokens": tok}, cache_len=L))
        lowered = fn.lower(full_params, _spec((1, 700), jnp.int32, one_chip))
    total = _total_bytes(lowered.compile())
    assert 8 * 2**30 < total < HBM_BYTES
