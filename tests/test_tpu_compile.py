"""Compile the served path's TPU programs for a described v5e chip.

Nothing runs here: the TPU compiler, which is installed with JAX, compiles
for a chip that is described and not attached. That catches what the Pallas
interpreter cannot (tile alignment, VMEM limits, a program that does not fit
the chip's 16 GiB) at no chip time. This is the only test file that describes
the chip. The topology is described inside a fixture, never at import time,
because only one process at a time may load the TPU library; keep every such
test in this file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import build_model
from repro.serving.engine import image_programs

HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip can be written to the persistent cache
    # but not read back without one: keep the cache off around these compiles
    prev_cache = jax.config.jax_enable_compilation_cache
    prev_log = os.environ.get("TPU_LOG_DIR")

    def restore():
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        cc.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log

    os.environ["TPU_LOG_DIR"] = "disabled"     # libtpu logs nowhere
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        restore()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        restore()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype), tree)


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _coder(layers: int, **kw):
    """DeepSeek-Coder-33B at every published width, cut in depth."""
    cfg = dataclasses.replace(get_config("deepseek_coder_33b"),
                              num_layers=layers)
    return build_model(cfg, **kw)


def _compile(model, sh, program: str, *, seq=2048, slots=4, max_len=4096):
    """Compile the engine's own prefill or decode program for ``model``."""
    prefill, decode = image_programs(model)
    params = _on(sh, model.abstract_params())
    if program == "prefill":
        return prefill.lower(params, {"tokens": _spec(sh, (1, seq), jnp.int32)}
                             ).compile()
    cache, _ = model.cache_specs(slots, max_len)
    fed = {"token": _spec(sh, (slots,), jnp.int32),
           "pos": _spec(sh, (slots,), jnp.int32)}
    return decode.lower(params, (_on(sh, cache), fed),
                        _spec(sh, (slots,), jnp.bool_)).compile()


def _kernel_case(name, sh):
    """(fn, arg specs) for one kernel at the widths chip_smoke.py runs."""
    c = get_config("deepseek_coder_33b")
    H, KV, hd = c.num_heads, c.num_kv_heads, c.head_dim
    m = get_config("falcon_mamba_7b").mamba
    if name in ("flash_attention", "flash_attention_window"):
        window = 1024 if name.endswith("window") else 0
        return (lambda q, k, v: ops.flash_attention(
                    q, k, v, causal=True, window=window, impl="pallas"),
                [_spec(sh, (1, 2048, H, hd)), _spec(sh, (1, 2048, KV, hd)),
                 _spec(sh, (1, 2048, KV, hd))])
    if name == "decode_attention":
        return (lambda q, k, v, p: ops.decode_attention(q, k, v, p,
                                                        impl="pallas"),
                [_spec(sh, (4, H, hd)), _spec(sh, (4, 4096, KV, hd)),
                 _spec(sh, (4, 4096, KV, hd)), _spec(sh, (4,), jnp.int32)])
    if name == "mamba_scan":
        DI, N = m.d_inner, m.d_state
        return (lambda dt, x, B, C, A, D: ops.mamba_scan(dt, x, B, C, A, D,
                                                         impl="pallas"),
                [_spec(sh, (1, 512, DI)), _spec(sh, (1, 512, DI)),
                 _spec(sh, (1, 512, N)), _spec(sh, (1, 512, N)),
                 _spec(sh, (DI, N), jnp.float32), _spec(sh, (DI,), jnp.float32)])
    assert name == "grouped_matmul"
    return (lambda x, w, b: ops.grouped_matmul(x, w, b, impl="pallas"),
            [_spec(sh, (1024, c.d_model)), _spec(sh, (8, c.d_model, 2048)),
             _spec(sh, (8,), jnp.int32)])


@pytest.mark.parametrize("name", ["flash_attention", "flash_attention_window",
                                  "decode_attention", "mamba_scan",
                                  "grouped_matmul"])
def test_kernel_compiles_for_tpu(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_engine_program_fits_one_chip(one_chip, program):
    """8 layers at full width: the depth chip_smoke.py serves."""
    compiled = _compile(_coder(8), one_chip, program)
    assert _total_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("program", ["prefill", "decode"])
@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_attn_impl_reaches_compiled_model(one_chip, program, impl):
    """attn_impl="pallas" puts the compiled kernels into the model's
    program; "ref" leaves a plain XLA program."""
    compiled = _compile(_coder(1, attn_impl=impl), one_chip, program)
    assert ("tpu_custom_call" in compiled.as_text()) == (impl == "pallas")


def test_image_params_draw_without_f32_copies(one_chip):
    """The engine's jitted init writes each leaf in bf16 with no f32
    temporaries: eager init held a 4.1 GiB f32 copy of the largest leaf."""
    model = _coder(8)
    key = _spec(one_chip, (2,), jnp.uint32)
    compiled = jax.jit(model.init_params).lower(key).compile()
    m = compiled.memory_analysis()
    params_bytes = sum(s.size * s.dtype.itemsize for s in
                       jax.tree.leaves(model.abstract_params()))
    # bf16 outputs (+ tile padding of the small leaves), not f32
    assert params_bytes <= m.output_size_in_bytes < params_bytes + 2**20
    assert m.temp_size_in_bytes < 64 * 2**20
    assert _total_bytes(compiled) < HBM_BYTES
