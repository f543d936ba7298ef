"""Work counts and the peaks table of the benchmark."""

from __future__ import annotations

import json

import jax
import pytest

from bench import harness, work


def test_stencil_bytes_are_the_required_passes():
    n = 512
    assert work.stencil("advec_u", n, "float32").bytes == 4 * n ** 3 * 4
    assert work.stencil("diff_uvw", n, "float32").bytes == 7 * n ** 3 * 4
    assert work.stencil("update", 256, "bfloat16").bytes == 10 * 256 ** 3 * 2
    assert work.stencil_step(n, "float32").bytes == 21 * n ** 3 * 4


def test_stencil_bytes_do_not_depend_on_the_launch_config():
    # the program's own cost model counts halo re-reads, which change with
    # the tiling; the benchmark's count takes no config at all
    from repro.core import get_kernel
    b = get_kernel("advec_u")
    models = {b.make_workload(cfg, (512,) * 3, "float32").hbm_bytes
              for cfg in ({**b.default_config(), "block_z": bz, "block_y": by}
                          for bz, by in ((8, 16), (16, 32), (32, 64)))}
    assert len(models) == 3
    assert work.stencil("advec_u", 512, "float32").bytes < min(models)


def test_stablelm_param_count_matches_the_built_model():
    from repro.models import build_model
    cfg = harness.load_json("configs", "stablelm-1.6b")
    serve = harness.load_module("drivers", "serve_batch")
    model = build_model(serve.program_arch(cfg))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    built = sum(x.size for x in jax.tree.leaves(shapes))
    assert work.lm_params(cfg) == built
    assert 1.6e9 < built < 1.7e9


def test_unknown_device_kind_raises():
    with pytest.raises(work.UnknownDeviceError):
        work.peaks("TPU v99")


def test_v5e_peaks_are_the_published_ones():
    pk = work.peaks("TPU v5 lite")
    assert (pk.flops_per_s, pk.hbm_bytes_per_s) == (197e12, 819e9)
    src = json.loads(work.PEAKS_FILE.read_text())["source"]
    assert "TPU v5e" in src


def test_least_time_takes_the_larger_bound():
    pk = work.Peaks("x", 100.0, 10.0)
    assert work.Work(1000.0, 10.0).least_s(pk) == 10.0       # FLOP bound
    assert work.Work(10.0, 100.0).least_s(pk) == 10.0        # byte bound
    assert work.Work(1000.0, 10.0).least_s(pk, flops=False) == 1.0


def test_decode_step_counts_live_cache_not_the_arena():
    cfg = harness.load_json("configs", "stablelm-1.6b")
    few = work.lm_decode_step(cfg, active=8, live=8 * 100)
    many = work.lm_decode_step(cfg, active=8, live=8 * 1000)
    row = 24 * 2 * 32 * 64 * 2
    assert many.bytes - few.bytes == 8 * 900 * row
    assert few.flops == 2.0 * work.lm_matmul_params(cfg) * 8 \
        + 4.0 * 24 * 2048 * 800
