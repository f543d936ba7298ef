#!/usr/bin/env python3
"""Chip smoke test: drive the launcher's main path once on one TPU v5e.

Phases, in one process and in this order (any failure raises, prints no
result line and exits non-zero):

  device      the first JAX device is a TPU v5e with published peaks, and
              kernels resolve to the Pallas (Mosaic) lowering;
  stencil     the paper's MicroHH kernels (advec_u, diff_uvw) at 256³
              float32: default-tier launch checked against ``ref.py``,
              captured, tuned on the chip by wall clock, relaunched from
              the new wisdom at tier ``exact``, then a 20-step time loop;
              plus default launches at 256³ bfloat16 and 512³ float32;
  lm_kernels  ``ops.matmul`` at 4096² bfloat16 and causal ``ops.attention``
              at (1, 32, 4096, 128) bfloat16 on the flash route;
  serve       ``ServeEngine`` on stablelm-1.6b at its published widths
              (random bfloat16 weights) answering 6 greedy requests, and
              decode-path logits compared with the full forward.

Each phase prints one ``phase=<name> {json}`` line. The last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``. Wisdom and a
JSON summary go to ``--out``; captures go to a temporary directory that is
deleted before exit. JAX's persistent compilation cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``<repo>/.jax_cache``. Times
are host-clock seconds around work that ends in ``block_until_ready``.

Run: python3 chip_smoke.py [--out DIR] [--seed N]
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.core import WisdomKernel, get_kernel, resolve_backend  # noqa: E402
from repro.core.capture import (CAPTURE_DIR_ENV, CAPTURE_ENV,  # noqa: E402
                                list_captures)
from repro.core.compile_cache import enable_persistent_cache  # noqa: E402
from repro.core.device import (DEVICE_ENV, current_device,  # noqa: E402
                               parse_device_kind)
from repro.core.wisdom import WISDOM_DIR_ENV  # noqa: E402
from repro.core.wisdom_kernel import BACKEND_ENV  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve import Request, ServeEngine  # noqa: E402
from repro.tuner.runner import tolerances  # noqa: E402
from repro.tuner.tune import tune_capture  # noqa: E402

DEFAULT_OUT = ROOT / "smoke_out"
STENCILS = ("advec_u", "diff_uvw")
STENCIL_GRID = 256
LARGE_GRID = 512
TUNE_EVALS = 8        # wall-clock evaluations per stencil tuning session
TIME_STEPS = 20
DT = 1e-3
MATMUL_N = 4096
ATTN_SHAPE = (1, 32, 4096, 128)
SERVE_ARCH = "stablelm-1.6b"
SERVE_SLOTS = 4
SERVE_MAX_SEQ = 512
SERVE_REQUESTS = 6
SERVE_NEW_TOKENS = 16
SERVE_PROMPT_LENS = (16, 64)
#: Largest max |decode logits - forward logits| over max |forward logits|
#: accepted for the bfloat16 model.
LOGITS_REL_TOL = 5e-2


class SmokeFailure(RuntimeError):
    """A phase found something wrong."""


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(phase: str, **fields) -> dict:
    print(f"phase={phase} {json.dumps(fields, sort_keys=True)}", flush=True)
    return fields


# -- correctness -------------------------------------------------------------


@jax.jit
def _leaf_error(got, want, rtol, atol):
    g = got.astype(jnp.float32)
    w = want.astype(jnp.float32)
    err = jnp.abs(g - w)
    scale = jnp.maximum(1.0, jnp.max(jnp.abs(w)))
    ok = jnp.all(err <= atol * scale + rtol * jnp.abs(w))
    return jnp.max(err), scale, ok, jnp.all(jnp.isfinite(g))


def compare(got, want, dtype: str) -> dict:
    """The oracle rule of ``tuner.runner.verify_outcome``, evaluated on the
    device: per output, |got - want| <= atol * max(1, max|want|) + rtol *
    |want|, with the dtype's tolerances."""
    rtol, atol = tolerances(dtype)
    got_l, want_l = jax.tree.leaves(got), jax.tree.leaves(want)
    require(len(got_l) == len(want_l), "output structure mismatch")
    max_err, max_scale, ok = 0.0, 1.0, True
    for g, w in zip(got_l, want_l):
        require(g.shape == w.shape, f"shape {g.shape} vs {w.shape}")
        e, s, leaf_ok, finite = _leaf_error(g, w, rtol, atol)
        require(bool(finite), "non-finite output")
        max_err, max_scale = max(max_err, float(e)), max(max_scale, float(s))
        ok = ok and bool(leaf_ok)
    return {"ok": ok, "max_err": max_err, "ref_scale": max_scale,
            "rtol": rtol, "atol": atol}


def reference(fn, *args):
    """``fn`` jitted at highest matmul precision: float32 is float32."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*args)


def checked(builder, args, got, what: str) -> dict:
    want = reference(builder.make_reference(), *args)
    c = compare(got, want, builder.get_dtype(*args))
    require(c["ok"], f"{what}: mismatch against the oracle: {c}")
    return c


# -- phases ------------------------------------------------------------------


def phase_device() -> dict:
    devs = jax.devices()
    d = devs[0]
    require(d.platform == "tpu",
            f"no TPU: the first JAX device is {d.platform!r} "
            f"({d.device_kind!r})")
    kind = parse_device_kind(d.device_kind, d.platform)
    spec = current_device()       # raises on a kind without published peaks
    require(kind == "tpu-v5e" and spec.kind == kind,
            f"device kind {d.device_kind!r} parses to {kind!r}, not tpu-v5e")
    require(not spec.estimated, f"{kind} has estimated peaks")
    backend = resolve_backend()
    require(backend == "pallas", f"kernel backend {backend!r}, not pallas")
    return emit("device", platform=d.platform, device_kind=d.device_kind,
                kind=kind, count=len(devs), backend=backend,
                peak_bf16_flops=spec.flops_bf16, hbm_bw=spec.hbm_bw)


def stencil_args(name: str, n: int, dtype, key) -> tuple:
    ku, kv, kw, ke = jax.random.split(key, 4)
    u, v, w = (jax.random.normal(k, (n, n, n), jnp.float32).astype(dtype)
               for k in (ku, kv, kw))
    scal = ops.pack_scalars(1.1, 0.9, 1.3)
    if name == "advec_u":
        return u, v, w, scal
    evisc = (jax.random.normal(ke, (n, n, n), jnp.float32) ** 2
             + 0.1).astype(dtype)
    return u, v, w, evisc, scal


def refusal_kind(error: str) -> str:
    """Class of an infeasible evaluation, from its error text."""
    if error == "restricted":
        return "restricted"
    if "vmem" in error.lower():
        return "vmem"
    if "allclose failed" in error:
        return "numerics"
    if "divisible by 8 and 128" in error:
        return "tiling"
    if "do not tile" in error:
        return "blocks"
    m = re.search(r"(\w+(?:Error|Exception))", error)
    return m.group(1) if m else "other"


@jax.jit
def _advec_step(args, ut):
    u, v, w, scal = args
    return (u + DT * ut).astype(u.dtype), v, w, scal


@jax.jit
def _diff_step(args, tend):
    u, v, w, evisc, scal = args
    u, v, w = ((f + DT * t).astype(f.dtype)
               for f, t in zip((u, v, w), tend))
    return u, v, w, evisc, scal


def time_loop(kernel: WisdomKernel, name: str, args: tuple,
              steps: int) -> dict:
    """The application's loop: launch, advance the fields, repeat."""
    step = _advec_step if name == "advec_u" else _diff_step
    n0 = len(kernel.stats)
    t0 = time.perf_counter()
    for _ in range(steps):
        args = step(args, kernel(*args))
    jax.block_until_ready(args)
    wall_s = time.perf_counter() - t0
    stats = kernel.stats[n0:]
    tiers = collections.Counter(s.tier for s in stats)
    compiles = sum(1 for s in stats if not s.cached)
    finite = all(bool(jnp.all(jnp.isfinite(a))) for a in args[:-1])
    require(tiers == {"exact": steps}, f"{name} loop tiers {dict(tiers)}")
    require(compiles == 0, f"{name} loop compiled {compiles} times")
    require(finite, f"{name} fields went non-finite in the time loop")
    # a launch returns once dispatched: its host time, not the device's
    dispatch = [s.dispatch_s for s in stats]
    return {"steps": steps, "tiers": dict(tiers), "compiles": compiles,
            "host_loop_s": wall_s,
            "host_dispatch_s_mean": float(np.mean(dispatch)),
            "host_dispatch_s_min": float(np.min(dispatch))}


def phase_stencil(name: str, seed: int, wisdom_dir: Path,
                  scratch: Path) -> dict:
    builder = get_kernel(name)
    grid = STENCIL_GRID
    key = jax.random.PRNGKey(seed)
    cap_dir = scratch / f"captures-{name}"
    empty = WisdomKernel(builder, wisdom_dir=scratch / "empty-wisdom")

    # default tier, captured as the application launched it
    args = stencil_args(name, grid, jnp.float32, key)
    os.environ[CAPTURE_ENV] = name
    os.environ[CAPTURE_DIR_ENV] = str(cap_dir)
    try:
        got = empty(*args)
    finally:
        del os.environ[CAPTURE_ENV], os.environ[CAPTURE_DIR_ENV]
    first = empty.stats[-1]
    require(first.tier == "default", f"{name}: first launch tier "
            f"{first.tier!r} with empty wisdom")
    default_check = checked(builder, args, got, f"{name} default")

    # other paper scenarios at the default config
    extra = {}
    for label, n, dtype in ((f"bf16_{grid}", grid, jnp.bfloat16),
                            (f"f32_{LARGE_GRID}", LARGE_GRID, jnp.float32)):
        xargs = stencil_args(name, n, dtype, key)
        xgot = empty(*xargs)
        require(empty.stats[-1].tier == "default",
                f"{name} {label}: tier {empty.stats[-1].tier!r}")
        extra[label] = checked(builder, xargs, xgot, f"{name} {label}")
        extra[label]["host_compile_s"] = empty.stats[-1].compile_s
        del xargs, xgot

    # tune the captured launch on the chip
    caps = list_captures(cap_dir)
    require(len(caps) == 1, f"{name}: {len(caps)} captures written")
    res = tune_capture(caps[0], "tpu-v5e", strategy="random",
                       max_evals=TUNE_EVALS, objective="wallclock",
                       wisdom_dir=wisdom_dir, seed=seed)
    feasible = res.feasible_evaluations
    refusals = collections.Counter(refusal_kind(e.error)
                                   for e in res.evaluations if not e.feasible)
    require(feasible and res.best_config is not None,
            f"{name}: no feasible config in {len(res.evaluations)} "
            f"evaluations; refusals {dict(refusals)}")

    # relaunch from the new wisdom
    tuned = WisdomKernel(builder, wisdom_dir=wisdom_dir)
    got = tuned(*args)
    last = tuned.stats[-1]
    require(last.tier == "exact", f"{name}: relaunch tier {last.tier!r}")
    require(last.config == res.best_config,
            f"{name}: relaunch config {last.config} != tuned "
            f"{res.best_config}")
    tuned_check = checked(builder, args, got, f"{name} tuned")
    loop = time_loop(tuned, name, args, TIME_STEPS)
    return emit(f"stencil.{name}", grid=grid, dtype="float32",
                default_config=first.config, default=default_check,
                host_default_compile_s=first.compile_s,
                evaluations=len(res.evaluations), feasible=len(feasible),
                refusals=dict(refusals), best_config=res.best_config,
                best_us=res.best_score_us, tuned=tuned_check,
                time_loop=loop, **extra)


def phase_lm_kernels(seed: int) -> dict:
    n, attn_shape = MATMUL_N, ATTN_SHAPE
    ka, kb, kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 5)
    a = jax.random.normal(ka, (n, n), jnp.float32).astype(jnp.bfloat16)
    b = jax.random.normal(kb, (n, n), jnp.float32).astype(jnp.bfloat16)
    got = ops.matmul(a, b)
    mm = compare(got, reference(ref.matmul_ref, a, b), "bfloat16")
    require(mm["ok"], f"matmul mismatch: {mm}")
    mm["tier"] = ops.matmul_kernel.last_tier

    q, k, v = (jax.random.normal(kk_, attn_shape, jnp.float32
                                 ).astype(jnp.bfloat16)
               for kk_ in (kq, kk, kv))
    before = ops.attention_routes.get("flash", 0)
    got = ops.attention(q, k, v, causal=True)
    flash_calls = ops.attention_routes.get("flash", 0) - before
    require(flash_calls >= 1,
            f"attention took no flash route: {ops.attention_routes}")
    at = compare(got, reference(
        lambda q, k, v: ref.attention_ref(q, k, v, causal=True), q, k, v),
        "bfloat16")
    require(at["ok"], f"attention mismatch: {at}")
    at["tier"] = ops.fa_causal_kernel.last_tier
    return emit("lm_kernels", matmul={"shape": [n, n, n], **mm},
                attention={"shape": list(attn_shape), **at},
                attention_routes=dict(ops.attention_routes))


def phase_serve(seed: int) -> dict:
    cfg = get_arch(SERVE_ARCH)
    n_slots, max_seq = SERVE_SLOTS, SERVE_MAX_SEQ
    n_requests, new_tokens = SERVE_REQUESTS, SERVE_NEW_TOKENS
    prompt_lens = SERVE_PROMPT_LENS
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(
        jax.random.PRNGKey(seed)))
    init_s = time.perf_counter() - t0
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)})

    rng = np.random.default_rng(seed)
    reqs = [Request(i, rng.integers(0, cfg.vocab,
                                    size=int(rng.integers(prompt_lens[0],
                                                          prompt_lens[1] + 1)),
                                    dtype=np.int32),
                    max_new_tokens=new_tokens)
            for i in range(n_requests)]
    eng = ServeEngine(model, params, n_slots=n_slots, max_seq=max_seq)
    for r in reqs:
        require(eng.submit(r), f"request {r.request_id} rejected")
    t0 = time.perf_counter()
    report = eng.run()
    serve_s = time.perf_counter() - t0
    served = sum(1 for r in reqs if len(report[r.request_id]) == new_tokens)
    require(served == n_requests,
            f"{served}/{n_requests} requests returned {new_tokens} tokens")

    # decode-path logits against the full forward on one prompt
    prompt = jnp.asarray(reqs[0].prompt)[None, :]
    decode = jax.jit(model.decode_step)
    cache = model.init_cache(1, max_seq)
    steps = []
    for t in range(prompt.shape[1]):
        logits, cache = decode(params, cache, prompt[:, t:t + 1])
        steps.append(logits[:, 0])
    dec = jnp.stack(steps, axis=1)[..., :cfg.vocab].astype(jnp.float32)

    @jax.jit
    def forward_logits(p, tokens):
        x, _ = model.forward(p, tokens)
        return model._head(p, x)[..., :cfg.vocab].astype(jnp.float32)

    fwd = forward_logits(params, prompt)
    max_err = float(jnp.max(jnp.abs(dec - fwd)))
    scale = float(jnp.max(jnp.abs(fwd)))
    argmax_agree = float(jnp.mean(jnp.argmax(dec, -1) == jnp.argmax(fwd, -1)))
    require(np.isfinite(max_err) and max_err <= LOGITS_REL_TOL * scale,
            f"decode vs forward logits: max abs err {max_err} over max "
            f"|logit| {scale}")
    return emit("serve", arch=cfg.name, n_params=n_params,
                param_dtypes=dtypes, n_slots=n_slots, max_seq=max_seq,
                requests=n_requests, served=served,
                prompt_lens=[len(r.prompt) for r in reqs],
                new_tokens=new_tokens, steps=report.steps, mode=report.mode,
                occupancy=report.occupancy, host_init_s=init_s,
                host_serve_s=serve_s,
                host_tokens_per_s=n_requests * new_tokens / serve_s,
                logits_positions=int(prompt.shape[1]),
                logits_max_abs_err=max_err, logits_max_abs=scale,
                logits_argmax_agree=argmax_agree)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", type=Path, default=DEFAULT_OUT,
                    help="directory for wisdom and summary.json")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated input and weight")
    args = ap.parse_args(argv)

    # the live device decides, not overrides from the environment
    for var in (DEVICE_ENV, BACKEND_ENV):
        os.environ.pop(var, None)
    t0 = time.perf_counter()
    summary = {"device": phase_device()}
    summary["compile_cache"] = str(enable_persistent_cache(
        ROOT / ".jax_cache"))
    wisdom_dir = args.out / "wisdom"
    shutil.rmtree(wisdom_dir, ignore_errors=True)
    wisdom_dir.mkdir(parents=True)
    os.environ[WISDOM_DIR_ENV] = str(wisdom_dir)
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as scratch:
        for name in STENCILS:
            summary[f"stencil.{name}"] = phase_stencil(
                name, args.seed, wisdom_dir, Path(scratch))
    summary["lm_kernels"] = phase_lm_kernels(args.seed)
    summary["serve"] = phase_serve(args.seed)
    summary["host_total_s"] = time.perf_counter() - t0
    (args.out / "summary.json").write_text(
        json.dumps(summary, indent=1, sort_keys=True))

    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
