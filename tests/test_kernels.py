"""Per-kernel correctness: shape/dtype sweeps, interpret-mode Pallas vs the
ref.py oracle (the assignment's per-kernel allclose requirement)."""

import numpy as np
import pytest

from repro.core import get_kernel
from repro.tuner.runner import verify_against_reference


def fields(rng, shape, dtype):
    return [rng.standard_normal(shape).astype(dtype) for _ in range(3)]


SCAL = np.array([[1.1, 0.9, 1.3, 0.0]], np.float32)


@pytest.mark.parametrize("shape", [(8, 8, 128), (16, 32, 128),
                                   (32, 16, 256)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_advec_u_shapes_dtypes(rng, shape, dtype):
    import jax.numpy as jnp
    b = get_kernel("advec_u")
    u, v, w = [np.asarray(jnp.asarray(f, dtype))
               for f in fields(rng, shape, np.float32)]
    # a tiling that fits every swept shape
    cfg = b.default_config() | {"block_z": 4, "block_y": 8}
    ok, msg = verify_against_reference(b, cfg, [u, v, w, SCAL])
    assert ok, msg


@pytest.mark.parametrize("config_update", [
    {"block_z": 8, "block_y": 16},
    {"block_z": 4, "block_y": 8, "traversal": "yz"},
    {"unroll_z": 2}, {"unroll_z": 4},
    {"dim_semantics": "parallel"},
])
def test_advec_u_config_sweep(rng, config_update):
    b = get_kernel("advec_u")
    cfg = b.default_config() | config_update
    u, v, w = fields(rng, (32, 32, 128), np.float32)
    ok, msg = verify_against_reference(b, cfg, [u, v, w, SCAL])
    assert ok, msg


@pytest.mark.parametrize("fuse", [True, False])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_diff_uvw(rng, fuse, dtype):
    import jax.numpy as jnp
    b = get_kernel("diff_uvw")
    u, v, w = [np.asarray(jnp.asarray(f, dtype))
               for f in fields(rng, (32, 32, 128), np.float32)]
    e = np.asarray(jnp.asarray(
        rng.standard_normal((32, 32, 128)) ** 2, dtype))
    cfg = b.default_config() | {"fuse_outputs": fuse}
    ok, msg = verify_against_reference(b, cfg, [u, v, w, e, SCAL])
    assert ok, msg


def test_diff_uvw_config_sweep(rng):
    b = get_kernel("diff_uvw")
    u, v, w = fields(rng, (32, 32, 128), np.float32)
    e = rng.standard_normal((32, 32, 128)).astype(np.float32) ** 2
    for cfg in b.space.sample(np.random.default_rng(3), 6):
        # block sizes must tile the 32x32 problem; skip invalid tilings
        if 32 % cfg["block_z"] or 32 % cfg["block_y"] or cfg["block_y"] > 32:
            continue
        ok, msg = verify_against_reference(b, cfg, [u, v, w, e, SCAL])
        assert ok, f"{cfg}: {msg}"


@pytest.mark.parametrize("mnk", [(128, 128, 256), (256, 512, 128),
                                 (64, 128, 1024)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_matmul_shapes_dtypes(rng, mnk, dtype):
    import jax.numpy as jnp
    m, n, k = mnk
    b = get_kernel("matmul")
    a = np.asarray(jnp.asarray(rng.standard_normal((m, k)), dtype))
    bb = np.asarray(jnp.asarray(rng.standard_normal((k, n)), dtype))
    ok, msg = verify_against_reference(b, b.default_config(), [a, bb])
    assert ok, msg


def test_matmul_grid_orders(rng):
    b = get_kernel("matmul")
    a = rng.standard_normal((256, 512)).astype(np.float32)
    bb = rng.standard_normal((512, 256)).astype(np.float32)
    for order in ("mnk", "nmk"):
        cfg = b.default_config() | {"grid_order": order, "block_m": 64,
                                    "block_n": 128, "block_k": 256}
        ok, msg = verify_against_reference(b, cfg, [a, bb])
        assert ok, f"{order}: {msg}"


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
def test_flash_attention_gqa(rng, causal, hq, hkv):
    name = "flash_attention_causal" if causal else "flash_attention_full"
    b = get_kernel(name)
    S, D = 256, 128
    q = rng.standard_normal((hq, S, D)).astype(np.float32)
    k = rng.standard_normal((hkv, S, D)).astype(np.float32)
    v = rng.standard_normal((hkv, S, D)).astype(np.float32)
    ok, msg = verify_against_reference(b, b.default_config(), [q, k, v])
    assert ok, msg


def test_flash_attention_block_sweep(rng):
    b = get_kernel("flash_attention_causal")
    q = rng.standard_normal((2, 512, 128)).astype(np.float32)
    k = rng.standard_normal((2, 512, 128)).astype(np.float32)
    v = rng.standard_normal((2, 512, 128)).astype(np.float32)
    for bq in (128, 256, 512):
        for bk in (128, 256):
            cfg = b.default_config() | {"block_q": bq, "block_k": bk}
            ok, msg = verify_against_reference(b, cfg, [q, k, v])
            assert ok, f"bq={bq} bk={bk}: {msg}"


def test_workloads_defined_for_all_kernels():
    from repro.core import all_kernels
    for name, b in all_kernels().items():
        cfg = b.default_config()
        problem = {"advec_u": (64, 64, 128), "diff_uvw": (64, 64, 128),
                   "matmul": (256, 256, 256),
                   "flash_attention_causal": (8, 2, 512, 128),
                   "flash_attention_full": (8, 2, 512, 128),
                   "decode_attention": (8, 32, 32, 64, 2048),
                   "mla_attention": (16, 128, 8192, 512, 64, 640)}[name]
        w = b.make_workload(cfg, problem, "float32")
        assert w.flops > 0 and w.hbm_bytes > 0 and w.vmem_bytes > 0
