"""Full causal GQA attention with RoPE.

q/k/v projections, RoPE on interleaved pairs (2i, 2i+1), causal
softmax(q k^T / sqrt(D)) v with each kv head serving ``q_heads / kv_heads``
consecutive q heads, output projection.  FLOPs: the four projections'
weights, and 4 * q_heads * head_dim per causal query-key pair.  A sliding
window (``window > 0``) needs its own equations and is refused.
"""
import math

import jax
import jax.numpy as jnp

from bench.layers import _lin, _mm, _rope

Q_BLOCK = 512


def _full(spec):
    if spec.get("window", 0) > 0:
        raise ValueError(f"mixer.full: window={spec['window']} is a sliding "
                         "window, which full attention does not compute")


def forward(p, x, spec, *, eps, low):
    _full(spec)
    B, T, _ = x.shape
    H, Hkv, D = spec["q_heads"], spec["kv_heads"], spec["head_dim"]
    q = _lin(x, p["wq"]["w"], low).reshape(B, T, H, D).transpose(0, 2, 1, 3)
    k = _lin(x, p["wk"]["w"], low).reshape(B, T, Hkv, D).transpose(0, 2, 1, 3)
    v = _lin(x, p["wv"]["w"], low).reshape(B, T, Hkv, D).transpose(0, 2, 1, 3)
    if spec.get("rope", True):
        q = _rope(q, spec["rope_theta"])
        k = _rope(k, spec["rope_theta"])
    g = H // Hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    outs = []
    for s in range(0, T, Q_BLOCK):                      # blocks of query rows
        qb = q[:, :, s:s + Q_BLOCK]
        sc = _mm("bhqd,bhkd->bhqk", qb, k, low) / math.sqrt(D)
        qpos = s + jnp.arange(qb.shape[2])[:, None]
        sc = jnp.where(jnp.arange(T)[None] <= qpos, sc, -jnp.inf)
        outs.append(_mm("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v, low))
    o = jnp.concatenate(outs, 2).transpose(0, 2, 1, 3).reshape(B, T, H * D)
    return _lin(o, p["wo"]["w"], low)


def matmul_params(spec, d_model):
    H, Hkv, D = spec["q_heads"], spec["kv_heads"], spec["head_dim"]
    return d_model * H * D * 2 + d_model * Hkv * D * 2


def pair_flops(spec, phase):
    _full(spec)
    return 4.0 * spec["q_heads"] * spec["head_dim"]
