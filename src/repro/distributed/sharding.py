"""Sharding rules: map every param/activation/cache leaf to a PartitionSpec.

Mesh axes (see launch/mesh.py):
  * "pod"   — hierarchical data parallelism across pods (multi-pod only)
  * "data"  — data parallelism within a pod
  * "model" — tensor parallelism (heads / d_ff / experts-dff / vocab)

Rules (MaxText-style, but derived from leaf path + shape):
  * embed / unembed: vocab dim over "model"
  * attention wq/wk/wv: output (heads*dim) over "model" when divisible,
    else replicated (GQA kv_heads < 16); wo: input over "model"
  * FFN w1/w3: d_ff over "model"; w2: d_ff (input) over "model"
  * MoE w1/w3/w2: d_ff dim over "model" (TP-within-expert — works for any
    expert count on a 16-way axis); router replicated
  * norms / biases / gates: replicated
  * batch dims of inputs & caches: over ("pod","data") when divisible
  * optional sequence parallelism: activations sharded on seq over "model"
"""
from __future__ import annotations

import re
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _divisible(dim: int, mesh: Mesh, axis: str) -> bool:
    return axis in mesh.shape and dim % mesh.shape[axis] == 0 and dim > 0


def _data_axes(mesh: Mesh):
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    return axes if axes else None


def _leaf_name(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _with_fsdp(spec: P, shape, mesh: Mesh) -> P:
    """Add ZeRO-3-style param sharding: pick the largest dim not already
    sharded and split it over "data" (XLA all-gathers per use). Essential
    to fit 100B+ param/optimizer state on 16 GB v5e chips."""
    if "data" not in mesh.shape:
        return spec
    specs = list(spec) + [None] * (len(shape) - len(spec))
    cands = [(shape[d], d) for d in range(len(shape))
             if specs[d] is None and _divisible(shape[d], mesh, "data")
             and shape[d] >= 2 * mesh.shape["data"]]
    if not cands:
        return spec
    _, d = max(cands)
    specs[d] = "data"
    return P(*specs)


def param_pspec(path: str, shape, mesh: Mesh, fsdp: bool = False) -> P:
    """PartitionSpec for a parameter leaf, by path + shape heuristics."""
    spec = _param_pspec_base(path, shape, mesh)
    if fsdp:
        spec = _with_fsdp(spec, shape, mesh)
    return spec


def _param_pspec_base(path: str, shape, mesh: Mesh) -> P:
    nd = len(shape)
    last = path.rsplit("/", 1)[-1]

    def model_ok(d):
        return _divisible(shape[d], mesh, "model")

    # --- embeddings ---------------------------------------------------------
    if last == "unembed" and nd == 2:              # (d, V): shard vocab
        return P(None, "model") if model_ok(1) else P(None, None)
    if last == "embed" and nd == 2:                # (V, d): shard vocab
        return P("model", None) if model_ok(0) else P(None, None)

    # --- MoE expert weights (E, d, f) / (E, f, d): shard d_ff ---------------
    if re.search(r"ffn/w[13]$", path) and nd == 3:
        return P(None, None, "model") if model_ok(2) else P(None, None, None)
    if path.endswith("ffn/w2") and nd == 3:
        return P(None, "model", None) if model_ok(1) else P(None, None, None)
    # stacked (R, E, d, f) variants (scan-stacked MoE)
    if re.search(r"ffn/w[13]$", path) and nd == 4:
        return P(None, None, None, "model") if model_ok(3) else P(*([None] * 4))
    if path.endswith("ffn/w2") and nd == 4:
        return P(None, None, "model", None) if model_ok(2) else P(*([None] * 4))
    if "router" in path:
        return P(*([None] * nd))

    # --- dense FFN (d, f) / (f, d), possibly stacked (R, ...) ---------------
    if re.search(r"(ffn|shared)/w[13]$", path):
        specs = [None] * nd
        if model_ok(nd - 1):
            specs[nd - 1] = "model"
        return P(*specs)
    if re.search(r"(ffn|shared)/w2$", path):
        specs = [None] * nd
        if model_ok(nd - 2):
            specs[nd - 2] = "model"
        return P(*specs)

    # --- attention / linear-mixer projections -------------------------------
    if re.search(r"w(q|k|v|q_a|q_b|kv_a|kv_b)(/w)?$", path) or \
            re.search(r"(g_proj|i_proj|f_proj|a_proj|b_proj|w_gates)/w$", path):
        specs = [None] * nd
        if model_ok(nd - 1):
            specs[nd - 1] = "model"                # shard output features
        return P(*specs)
    if re.search(r"wo/w$", path):
        specs = [None] * nd
        if model_ok(nd - 2):
            specs[nd - 2] = "model"                # shard input features
        return P(*specs)

    # --- everything else (norms, biases, gates, convs) ----------------------
    return P(*([None] * nd))


def params_shardings(params, mesh: Mesh, fsdp: bool = False):
    """Pytree of NamedSharding matching ``params`` (works on shape structs)."""

    def spec(path, leaf):
        return NamedSharding(mesh, param_pspec(_leaf_name(path), leaf.shape,
                                               mesh, fsdp=fsdp))

    return jax.tree_util.tree_map_with_path(spec, params)


# ---------------------------------------------------------------------------
# batch / cache shardings
# ---------------------------------------------------------------------------


def batch_pspec(shape, mesh: Mesh, seq_axis: Optional[int] = None,
                shard_seq_over_data: bool = False) -> P:
    """Shard leading batch dim over ("pod","data"); optionally shard a seq
    axis over "data" (long-context decode with batch=1)."""
    axes = _data_axes(mesh)
    nd = len(shape)
    specs = [None] * nd
    if axes:
        total = int(np.prod([mesh.shape[a] for a in axes]))
        if shape[0] % total == 0 and shape[0] >= total:
            specs[0] = axes if len(axes) > 1 else axes[0]
    if (shard_seq_over_data and seq_axis is not None and specs[0] is None
            and "data" in mesh.shape
            and shape[seq_axis] % mesh.shape["data"] == 0):
        specs[seq_axis] = "data"
    return P(*specs)


def batch_shardings(batch, mesh: Mesh):
    def spec(path, leaf):
        return NamedSharding(mesh, batch_pspec(leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(spec, batch)


def cache_shardings(caches, mesh: Mesh, shard_seq_over_data: bool = False,
                    shard_headdim: bool = False):
    """Decode caches: (R, B, S, ...) — batch dim is axis 1; for batch=1
    long-context, shard the seq axis (flash-decode style) instead."""

    def spec(path, leaf):
        name = _leaf_name(path).rsplit("/", 1)[-1]
        shape = leaf.shape
        nd = len(shape)
        specs = [None] * nd
        axes = _data_axes(mesh)
        if axes and nd >= 2:
            total = int(np.prod([mesh.shape[a] for a in axes]))
            if shape[1] % total == 0 and shape[1] >= total:
                specs[1] = axes if len(axes) > 1 else axes[0]
            elif (shard_seq_over_data and name in ("k", "v", "ckv", "kpe")
                  and nd >= 3 and "data" in mesh.shape
                  and shape[2] % mesh.shape["data"] == 0):
                specs[2] = "data"
        # shard kv heads / head-state over model where divisible
        if name in ("k", "v") and nd >= 4 and _divisible(shape[3], mesh,
                                                         "model"):
            specs[3] = "model"
        elif (shard_headdim and name in ("k", "v") and nd >= 5
                and _divisible(shape[4], mesh, "model")):
            # GQA with kv_heads < |model|: shard head_dim (contracting dim;
            # XLA emits partial scores + all-reduce) instead of replicating
            specs[4] = "model"
        if name == "state" and nd >= 3 and _divisible(shape[2], mesh,
                                                      "model"):
            specs[2] = "model"
        return NamedSharding(mesh, P(*specs))

    return jax.tree_util.tree_map_with_path(spec, caches)
