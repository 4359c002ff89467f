"""Compile for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: block shapes that are
not legal tiles, scalar stores to VMEM, programs larger than the chip's
memory. These tests compile the four Pallas kernels at the widths of the
two configs the chip smoke runs, and the smoke's mamba2-370m train step
and hymba-1.5b decode step at its exact shapes. Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker
imports this file.
"""
import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.rmsnorm.kernel import fused_residual_rmsnorm
from repro.kernels.ssd.kernel import ssd_pallas
from repro.models import Model
from repro.optim import OptConfig, adamw_init
from repro.runtime import make_decode_fn, make_prefill_fn, make_train_step

ROOT = Path(__file__).resolve().parent.parent
HBM_BYTES = 16e9                                  # one v5e chip

_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)                   # standard library only

# (ssd heads, ssd head dim, ssd state, attention q heads, kv heads, head dim,
# d_model) of the two configs
WIDTHS = {name: (c.ssm_heads, c.ssm_headdim, c.ssm_state, c.n_heads,
                 c.n_kv_heads, c.d_head, c.d_model)
          for name in ("mamba2-370m", "hymba-1.5b")
          for c in [configs.get(name)]}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                        # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shapes(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                       sharding=sharding),
                        tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("arch", sorted(WIDTHS))
@pytest.mark.parametrize("chunk", [128, 256])
def test_ssd_kernel_compiles(one_chip, arch, chunk):
    h, p, n = WIDTHS[arch][:3]
    b, s = 2, 1024
    bf = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                             sharding=one_chip)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    _compile(lambda x, dt, A, B, C: ssd_pallas(x, dt, A, B, C, chunk=chunk),
             bf(b, s, h, p), f32(b, s, h), f32(h), bf(b, s, h, n),
             bf(b, s, h, n))


@pytest.mark.parametrize("arch", ["hymba-1.5b"])
def test_attention_kernels_compile(one_chip, arch):
    hq, hkv, d = WIDTHS[arch][3:6]
    b, s = 8, smoke.PROMPT_LEN
    bf = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                             sharding=one_chip)
    _compile(flash_attention, bf(b, hq, s, d), bf(b, hkv, s, d),
             bf(b, hkv, s, d))
    _compile(decode_attention, bf(b, hq, 1, d), bf(b, hkv, s, d),
             bf(b, hkv, s, d),
             jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_rmsnorm_kernel_compiles(one_chip, arch):
    d = WIDTHS[arch][6]
    rows = jax.ShapeDtypeStruct((8 * 2048, d), jnp.bfloat16, sharding=one_chip)
    _compile(fused_residual_rmsnorm, rows, rows,
             jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip))


def test_smoke_train_step_fits_one_chip(one_chip):
    model = Model(configs.get(smoke.TRAIN_ARCH))
    params = _shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    opt = _shapes(jax.eval_shape(adamw_init, params), one_chip)
    tokens = jax.ShapeDtypeStruct((smoke.TRAIN_BATCH, smoke.TRAIN_SEQ + 1),
                                  jnp.int32, sharding=one_chip)
    step_idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = make_train_step(model, OptConfig()).lower(
        params, opt, {"tokens": tokens}, step_idx).compile()
    assert _total_bytes(compiled) < HBM_BYTES


def test_smoke_decode_step_fits_one_chip(one_chip):
    model = Model(configs.get(smoke.SERVE_ARCH))
    params = _shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    prompts = jax.ShapeDtypeStruct((smoke.SERVE_BATCH, smoke.PROMPT_LEN),
                                   jnp.int32)
    prefill = make_prefill_fn(model, smoke.PROMPT_LEN + smoke.MAX_NEW)
    cache = _shapes(jax.eval_shape(lambda p, b: prefill(p, b)[1], params,
                                   {"tokens": prompts}), one_chip)
    token = jax.ShapeDtypeStruct((smoke.SERVE_BATCH, 1), jnp.int32,
                                 sharding=one_chip)
    compiled = make_decode_fn(model).lower(params, cache, token).compile()
    assert _total_bytes(compiled) < HBM_BYTES
