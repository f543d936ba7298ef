"""Sharding rules: parameter / optimizer / batch / cache PartitionSpecs.

Scheme (DESIGN.md §6):
  * batch over ("pod", "data")  — data parallelism;
  * tensor parallelism over "model": attention heads, d_ff, vocab, and the
    MoE expert axis (expert parallelism -> all-to-all dispatch);
  * FSDP over "data" (and "pod" for >=30B params): the non-TP dim of every
    weight is sharded and gathered per-layer inside the scan;
  * KV caches: batch over dp; heads over "model" when divisible, else the
    sequence axis (flash-decoding-style partial reductions).

Every proposed axis is divisibility-checked against the actual dim and
dropped if it does not divide — that is what makes one rule table serve all
10 architectures (e.g. hymba's vocab 32001 silently falls back to
replicated-vocab embedding).
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import ArchConfig

from .mesh import dp_axes

# leaf name -> (role), resolved against the last two (or one) dims
_COL = {"wq", "wk", "wv", "w_in", "w_gate", "shared_in", "shared_gate",
        "wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "w_r", "w_k", "w_v",
        "w_g", "cm_r", "cm_k", "decay_a", "decay_b"}
_ROW = {"wo", "w_out", "shared_out", "cm_v", "w_o"}
_VEC_MODEL = {"bq", "bk", "bv", "dt_bias", "D_skip", "bonus", "decay_base"}
_REPL = {"scale", "bias", "gate", "mu", "mu_c", "pos_embed", "dec_pos",
         "enc_pos"}
_MODEL_DIM2 = {"A_log", "w_bcdt"}      # (..., di, small): model on dim -2
_MODEL_LAST = {"conv_w"}               # (..., small, di): model on dim -1


def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    out = 1
    for a in axes:
        out *= mesh.shape[a] if a in mesh.axis_names else 1
    return out


def _fit(mesh, dim: int, axes):
    """axes if they divide dim and exist in the mesh, else None."""
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    axes = tuple(a for a in axes if a in mesh.axis_names)
    if not axes:
        return None
    if dim % _axis_size(mesh, axes):
        return None
    return axes if len(axes) > 1 else axes[0]


def _spec(mesh, shape, assignment: dict[int, Any]) -> P:
    """assignment: dim index -> proposed axes (checked + fallback None)."""
    entries = []
    for i, d in enumerate(shape):
        ax = assignment.get(i)
        ax = _fit(mesh, d, ax) if ax is not None else None
        entries.append(ax)
    return P(*entries)


def fsdp_axes(mesh, cfg: ArchConfig | None = None):
    if cfg is not None and cfg.n_params() >= 30_000_000_000 \
            and "pod" in mesh.axis_names:
        return ("pod", "data")
    return ("data",)


# Serving: if the TP-only (model-axis) shard of the weights fits comfortably
# in HBM, drop the FSDP dim — per-token weight all-gathers dominate decode
# otherwise (EXPERIMENTS.md §Perf, codeqwen decode cell).
SERVE_TP_BUDGET_BYTES = 6e9


def serve_tp_only(mesh, cfg: ArchConfig | None) -> bool:
    if cfg is None or "model" not in mesh.axis_names:
        return False
    bytes_per_param = 2 if cfg.param_dtype == "bfloat16" else 4
    per_chip = cfg.n_params() * bytes_per_param / mesh.shape["model"]
    return per_chip <= SERVE_TP_BUDGET_BYTES


def _param_spec(path_names: tuple[str, ...], leaf, mesh,
                cfg: ArchConfig | None, serve: bool = False) -> P:
    name = path_names[-1]
    shape = leaf.shape
    nd = len(shape)
    fsdp = None if (serve and serve_tp_only(mesh, cfg)) \
        else fsdp_axes(mesh, cfg)
    in_moe = "moe" in path_names
    if name == "embed":
        if cfg is not None and cfg.tie_embeddings:
            # vocab-sharded so the (transposed) LM head keeps logits
            # sharded over "model"; the lookup pays a reshard.
            return _spec(mesh, shape, {0: "model", 1: fsdp})
        # untied: shard d_model only -> communication-free gather; the
        # separate head carries the vocab sharding.
        return _spec(mesh, shape, {1: ("data", "model")})
    if name == "head":
        return _spec(mesh, shape, {0: fsdp, 1: "model"})
    if name == "router":
        return _spec(mesh, shape, {nd - 2: fsdp})
    if in_moe and name in ("w_in", "w_gate"):
        # (L, E, D, Fe): expert parallelism on E, FSDP on D
        return _spec(mesh, shape, {nd - 3: "model", nd - 2: fsdp})
    if in_moe and name == "w_out":
        # (L, E, Fe, D)
        return _spec(mesh, shape, {nd - 3: "model", nd - 1: fsdp})
    if name in _COL:
        if nd == 1:
            return _spec(mesh, shape, {0: "model"})
        return _spec(mesh, shape, {nd - 2: fsdp, nd - 1: "model"})
    if name in _ROW:
        return _spec(mesh, shape, {nd - 2: "model", nd - 1: fsdp})
    if name in _VEC_MODEL:
        return _spec(mesh, shape, {nd - 1: "model"})
    if name in _MODEL_DIM2:
        return _spec(mesh, shape, {nd - 2: "model"})
    if name in _MODEL_LAST:
        return _spec(mesh, shape, {nd - 1: "model"})
    if name == "scale" and "ln_x" in path_names:
        return _spec(mesh, shape, {nd - 1: "model"})
    # default: replicated (norm scales, gates, mixing vectors, counts)
    return P(*([None] * nd))


def _path_names(path) -> tuple[str, ...]:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
        else:  # pragma: no cover
            names.append(str(k))
    return tuple(names)


def param_pspecs(params_shape, mesh, cfg: ArchConfig | None = None,
                 serve: bool = False):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _param_spec(_path_names(path), leaf, mesh, cfg,
                                       serve),
        params_shape)


def opt_pspecs(opt_shape, mesh, cfg: ArchConfig | None = None):
    """m/v mirror the parameter specs; count replicated."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: (
            P() if _path_names(path)[-1] == "count"
            else _param_spec(_path_names(path)[1:], leaf, mesh, cfg)),
        opt_shape)


def state_pspecs(state_shape, mesh, cfg: ArchConfig | None = None):
    return {
        "params": param_pspecs(state_shape["params"], mesh, cfg),
        "opt": opt_pspecs(state_shape["opt"], mesh, cfg),
        "step": P(),
    }


def batch_pspecs(batch_shape, mesh):
    dp = dp_axes(mesh)

    def spec(leaf):
        if leaf.ndim == 0:
            return P()
        return _spec(mesh, leaf.shape, {0: dp})

    return jax.tree.map(spec, batch_shape)


def _cache_spec(path_names, leaf, mesh) -> P:
    name = path_names[-1]
    shape = leaf.shape
    nd = len(shape)
    dp = dp_axes(mesh)
    if nd == 0:
        return P()
    if name in ("k", "v"):
        # kv arenas (L, B, H, D, S); cross_kv stacks (L, B, H, M, D)
        seq = 3 if "cross_kv" in path_names else 4
        assign = {1: dp}
        if _fit(mesh, shape[2], "model"):
            assign[2] = "model"
        else:
            assign[seq] = "model" if _fit(mesh, shape[1], dp) else \
                ("data", "model")
        return _spec(mesh, shape, assign)
    if name == "latent":
        # MLA latent arena (L, B, S, kv_lora + d_rope)
        assign = {1: dp, 2: "model"}
        if not _fit(mesh, shape[1], dp):
            assign = {2: ("data", "model")}
        return _spec(mesh, shape, assign)
    if name == "ssm":
        return _spec(mesh, shape, {1: dp, 2: "model"})
    if name == "conv":
        return _spec(mesh, shape, {1: dp, 3: "model"})
    if name == "wkv":
        return _spec(mesh, shape, {1: dp, 2: "model"})
    if name in ("prev_t", "prev_c"):
        return _spec(mesh, shape, {1: dp, 2: "model"})
    return P(*([None] * nd))


def cache_pspecs(cache_shape, mesh):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _cache_spec(_path_names(path), leaf, mesh),
        cache_shape)


def logits_pspec(mesh, batch: int | None = None,
                 vocab: int | None = None):
    if batch is None or vocab is None:
        return P(dp_axes(mesh), None, "model")
    return _spec(mesh, (batch, 1, vocab), {0: dp_axes(mesh), 2: "model"})


def to_named(tree_of_pspecs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_of_pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def validate_specs(shapes_tree, specs_tree, mesh) -> list[str]:
    """Sanity: every sharded dim divisible. Returns list of violations."""
    errors = []

    def check(path, leaf, spec):
        for i, ax in enumerate(spec):
            if ax is None:
                continue
            size = _axis_size(mesh, ax)
            if leaf.shape[i] % size:
                errors.append(
                    f"{'/'.join(_path_names(path))}: dim {i} "
                    f"({leaf.shape[i]}) not divisible by {ax} ({size})")

    jax.tree_util.tree_map_with_path(check, shapes_tree, specs_tree)
    return errors
