"""Compile the served path and the Pallas kernels for a TPU v5e that is
described, not attached: what the chip's compiler refuses fails here.

The topology is described inside module fixtures (never at import), so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU compiler.  Shapes come from ``jax.eval_shape``;
nothing is placed on a device.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.distributed.sharding import unbox
from repro.kernels import flash_attention, paged_attention, ssd_scan
from repro.models import build_model
from repro.serving import engine as E

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One v5e chip, with the persistent compilation cache off: a compile
    for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_olmo_1b_served_steps_compile_for_v5e(one_chip, step):
    """The engine's own jitted steps at olmo-1b's published widths (depth
    cut to 2 layers): prefill of a 512-token prompt at batch 1, decode at
    batch 8 over a 2048-token slot cache."""
    cfg = dataclasses.replace(get_config("olmo-1b"), num_layers=2)
    model = build_model(cfg)
    params = on(one_chip, jax.eval_shape(
        lambda k: unbox(model.init(k)), jax.random.PRNGKey(0)))
    i32 = jnp.int32
    if step == "prefill":
        cache = on(one_chip, jax.eval_shape(lambda: model.init_cache(1, 2048)))
        lowered = E._prefill_step.lower(
            model, params,
            jax.ShapeDtypeStruct((1, 512), i32, sharding=one_chip), cache)
    else:
        cache = on(one_chip, jax.eval_shape(lambda: model.init_cache(8, 2048)))
        vec = jax.ShapeDtypeStruct((8,), i32, sharding=one_chip)
        lowered = E._decode_step.lower(model, params, vec, cache, vec)
    assert device_bytes(lowered.compile()) < V5E_HBM_BYTES


def _flash(one_chip):
    q = jax.ShapeDtypeStruct((1, 2048, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    return (lambda q, k, v: flash_attention(q, k, v, scale=128 ** -0.5),
            (q, q, q))


def _paged(one_chip):
    bf, i32 = jnp.bfloat16, jnp.int32
    pages = jax.ShapeDtypeStruct((1024, 16, 16, 128), bf, sharding=one_chip)
    return (lambda q, kp, vp, pt, ln: paged_attention(q, kp, vp, pt, ln,
                                                      scale=128 ** -0.5),
            (jax.ShapeDtypeStruct((8, 16, 128), bf, sharding=one_chip),
             pages, pages,
             jax.ShapeDtypeStruct((8, 128), i32, sharding=one_chip),
             jax.ShapeDtypeStruct((8,), i32, sharding=one_chip)))


def _ssd(one_chip):
    # mamba2-780m widths: 48 heads of 64 channels, state 128, chunk 256
    B, S, H, P, N = 1, 2048, 48, 64, 128
    bf, f32 = jnp.bfloat16, jnp.float32
    bc = jax.ShapeDtypeStruct((B, S, 1, N), bf, sharding=one_chip)
    return (lambda x, dt, A, Bm, Cm: ssd_scan(x, dt, A, Bm, Cm, chunk=256),
            (jax.ShapeDtypeStruct((B, S, H, P), bf, sharding=one_chip),
             jax.ShapeDtypeStruct((B, S, H), f32, sharding=one_chip),
             jax.ShapeDtypeStruct((H,), f32, sharding=one_chip), bc, bc))


@pytest.mark.parametrize("kernel", [_flash, _paged, _ssd],
                         ids=["flash_attention", "paged_attention",
                              "ssd_scan"])
def test_pallas_kernel_compiles_for_v5e(one_chip, kernel):
    fn, args = kernel(one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert device_bytes(compiled) < V5E_HBM_BYTES
