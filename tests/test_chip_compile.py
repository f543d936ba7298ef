"""AOT-compile the main path for a described TPU v5e (no chip attached).

Interpret-mode tests cannot see what Mosaic refuses: a block whose last two
dimensions are not tile-aligned, or a kernel that needs more VMEM than a
core has. These tests hand the real TPU compiler the default configs at the
paper's grids and the LM kernels and decode step at model widths. The
topology is described inside a module fixture: only the worker that runs
this file loads the TPU compiler.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core import get_kernel
from repro.models import build_model

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def v5e(monkeypatch):
    """Lower kernels with Mosaic compiler params, as on the chip."""
    monkeypatch.setenv("KERNEL_LAUNCHER_DEVICE", "tpu-v5e")


def _compile_default(name, shapes, one_chip, update=None):
    b = get_kernel(name)
    meta = tuple(jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                 for s, d in shapes)
    fn = b.make(b.default_config() | (update or {}), meta)
    return jax.jit(fn).lower(*meta).compile()


@pytest.mark.parametrize("name", ["advec_u", "diff_uvw"])
@pytest.mark.parametrize("n,dtype,tile", [
    pytest.param(256, jnp.float32, None, id="256-float32"),
    pytest.param(256, jnp.bfloat16, None, id="256-bfloat16"),
    pytest.param(512, jnp.float32, None, id="512-float32"),
    # the former default, still in the tuning space
    pytest.param(512, jnp.float32, {"block_z": 8, "block_y": 16},
                 id="512-float32-8x16")])
def test_stencil_default_compiles(name, n, dtype, tile, one_chip, v5e):
    n_fields = 3 if name == "advec_u" else 4
    shapes = [((n, n, n), dtype)] * n_fields + [((1, 4), jnp.float32)]
    compiled = _compile_default(name, shapes, one_chip, tile)
    assert "tpu_custom_call" in compiled.as_text()


def test_matmul_default_compiles_8192_f32(one_chip, v5e):
    shapes = [((8192, 8192), jnp.float32)] * 2
    compiled = _compile_default("matmul", shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_causal_default_compiles(one_chip, v5e):
    shapes = [((32, 4096, 128), jnp.bfloat16)] * 3
    compiled = _compile_default("flash_attention_causal", shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_stablelm_decode_step_compiles_full_width(one_chip, v5e,
                                                  monkeypatch):
    """The serve cell's step (8 slots x 2048, per-slot ``start``, cache
    donated) reads the KV arena in place: the arena is aliased, the
    decode-attention kernel is in the program, and no copy as large as
    one layer's arena is left (per-layer relayout copies stayed under
    donation alone while XLA laid out the in-loop attention)."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    model = build_model(get_arch("stablelm-1.6b"))
    slots, max_seq = 8, 2048

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: model.init_cache(slots, max_seq))
    cache["start"] = jax.ShapeDtypeStruct((slots,), jnp.int32)
    cache = on_chip(cache)
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, tokens).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES
    kv_bytes = sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(cache["kv"]))
    assert mem.alias_size_in_bytes >= kv_bytes
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    layer_arena = slots * model.cfg.n_kv_heads * max_seq * model.cfg.d_head
    copies = re.findall(r"= \w+\[([\d,]*)\]\S* copy\(", text)
    big = [c for c in copies
           if math.prod(int(d) for d in c.split(",") if d) >= layer_arena]
    assert not big, f"arena-sized copies: {big}"


def test_deepseek_v2_decode_step_compiles_full_width(one_chip, v5e,
                                                     monkeypatch):
    """The serve-prompt-heavy cell's step: DeepSeek-V2 at published widths
    cut to 6 layers (dense layer 0 and 5 MoE layers holding experts
    0-19 of 160), 16 slots x 8192, per-slot ``start``, cache donated,
    every token's experts kept as the benchmark keeps them. The latent
    arena and the experts' record are aliased, the latent kernel is in
    the program, and no buffer of the arena's size is copied: each layer
    reads the arena where it lies and the step writes its latent rows
    once."""
    import dataclasses
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    full = get_arch("deepseek-v2-236b")
    cfg = dataclasses.replace(full, n_layers=6, moe=dataclasses.replace(
        full.moe, held=(0, 20)))
    model = dataclasses.replace(build_model(cfg), record_routing=True)
    slots, max_seq = 16, 8192

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: model.init_cache(slots, max_seq))
    cache["start"] = jax.ShapeDtypeStruct((slots,), jnp.int32)
    cache = on_chip(cache)
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, tokens).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES
    arena = cache["mla"]["latent"]
    arena_bytes = arena.size * arena.dtype.itemsize
    picks = cache["moe_picks"]
    assert mem.alias_size_in_bytes >= arena_bytes \
        + picks.size * picks.dtype.itemsize
    assert mem.temp_size_in_bytes < arena_bytes // 6
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    layer_arena = slots * max_seq * arena.shape[-1]
    copies = re.findall(r"= \w+\[([\d,]*)\]\S* copy\(", text)
    big = [c for c in copies
           if math.prod(int(d) for d in c.split(",") if d) >= layer_arena]
    assert not big, f"arena-sized copies: {big}"
