"""The plain reference: the configuration's forward pass in float32 jnp.

It imports nothing of the program.  It reads the configuration file's
``model`` section and the weights ``bench/weights.py`` made (upcast to
float32 as they are used), and runs every matmul at
``Precision.HIGHEST``.  The layer equations are those the program serves
(one pre-norm residual stack of RMSNorm, mixer, optional SwiGLU FFN):

* attention: q/k/v projections, RoPE on interleaved pairs (2i, 2i+1),
  causal softmax(q k^T / sqrt(D)) v with each kv head serving
  ``q_heads / kv_heads`` consecutive q heads, output projection;
* Mamba2: q/k/v projections, a depthwise causal conv of width K over
  [q, k, v] and SiLU, k scaled by 1/sqrt(dk), per-head decay
  a_t = exp(-exp(A_log) * softplus(x W_a + dt_bias)), the recurrence
  S_t = a_t S_{t-1} + k_t^T v_t, o_t = q_t S_t + D v_t, a per-head RMSNorm
  of o, the gate SiLU(x W_g) and the output projection.  The recurrence is
  evaluated exactly, a chunk of 64 steps at a time (intra-chunk decayed
  products plus the carried state), all in float32.

``low=True`` is the control: the same equations with every matmul operand
rounded to float8 e4m3 under a per-tensor scale, the precision below the
configuration's bfloat16 that would tempt a later change.

Sequences are teacher-forced: ``tokens`` (B, T) holds each prompt followed by
the tokens the program served; ``read`` (B, P) the positions whose next-token
logits are wanted.  Padding past a sequence's end cannot reach earlier
positions (everything is causal).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
SSD_CHUNK = 64
Q_BLOCK = 512
F8_MAX = 448.0


def _fp8(x):
    s = jnp.max(jnp.abs(x)) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, low):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _lin(x, w, low):
    return _mm("...i,io->...o", x, w, low)


def _rope(x, theta):
    """x: (B, H, T, D); rotates pairs (2i, 2i+1) by position * theta^(-2i/D)."""
    B, H, T, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     -1).reshape(B, H, T, D)


def _attention(p, x, mixer, low):
    B, T, _ = x.shape
    H, Hkv, D = mixer["q_heads"], mixer["kv_heads"], mixer["head_dim"]
    q = _lin(x, p["wq"]["w"], low).reshape(B, T, H, D).transpose(0, 2, 1, 3)
    k = _lin(x, p["wk"]["w"], low).reshape(B, T, Hkv, D).transpose(0, 2, 1, 3)
    v = _lin(x, p["wv"]["w"], low).reshape(B, T, Hkv, D).transpose(0, 2, 1, 3)
    if mixer.get("rope", True):
        q = _rope(q, mixer["rope_theta"])
        k = _rope(k, mixer["rope_theta"])
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


def _ssd(q, k, v, log_a, low):
    """Exact S_t = a_t S_{t-1} + k_t^T v_t, o_t = q_t S_t from S_0 = 0.
    q, k: (B, H, T, dk); v: (B, H, T, dv); log_a: (B, H, T)."""
    B, H, T, dk = q.shape
    dv = v.shape[-1]
    c = SSD_CHUNK
    n = T // c

    def split(x):
        return jnp.moveaxis(x.reshape(B, H, n, c, *x.shape[3:]), 2, 0)

    qs, ks, vs, las = split(q), split(k), split(v), split(log_a)
    tri = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]

    def body(S, xs):
        qc, kc, vc, la = xs
        cum = jnp.cumsum(la, -1)                              # (B, H, c)
        diff = jnp.where(tri, cum[..., :, None] - cum[..., None, :], -jnp.inf)
        sc = _mm("bhtd,bhsd->bhts", qc, kc, low) * jnp.exp(diff)
        o = _mm("bhts,bhsv->bhtv", sc, vc, low) \
            + jnp.exp(cum)[..., None] * _mm("bhtd,bhdv->bhtv", qc, S, low)
        w = jnp.exp(cum[..., -1:] - cum)[..., None]
        S = jnp.exp(cum[..., -1])[..., None, None] * S \
            + _mm("bhsd,bhsv->bhdv", kc * w, vc, low)
        return S, o

    _, o = jax.lax.scan(body, jnp.zeros((B, H, dk, dv), jnp.float32),
                        (qs, ks, vs, las))
    return jnp.moveaxis(o, 0, 2).reshape(B, H, T, dv)


def _mamba2(p, x, mixer, eps, low):
    B, T, _ = x.shape
    H, dk, dv, K = (mixer["heads"], mixer["key_dim"], mixer["value_dim"],
                    mixer["conv_kernel"])
    z = jnp.concatenate([_lin(x, p["wq"]["w"], low),
                         _lin(x, p["wk"]["w"], low),
                         _lin(x, p["wv"]["w"], low)], -1)
    w = p["conv_w"].astype(jnp.float32)                       # (K, C)
    zp = jnp.pad(z, ((0, 0), (K - 1, 0), (0, 0)))
    z = jax.nn.silu(sum(zp[:, j:j + T] * w[j] for j in range(K)))

    def heads(t, d):
        return t.reshape(B, T, H, d).transpose(0, 2, 1, 3)

    q = heads(z[..., :H * dk], dk)
    k = heads(z[..., H * dk:2 * H * dk], dk) * dk ** -0.5
    v = heads(z[..., 2 * H * dk:], dv)
    dt = jax.nn.softplus(_lin(x, p["a_proj"]["w"], low)
                         + p["dt_bias"].astype(jnp.float32))
    log_a = (-jnp.exp(p["A_log"].astype(jnp.float32)) * dt).transpose(0, 2, 1)
    o = _ssd(q, k, v, log_a, low) \
        + p["D_skip"].astype(jnp.float32)[None, :, None, None] * v
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    o = o * p["g_norm"].astype(jnp.float32).reshape(1, H, 1, dv)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, H * dv)
    gate = jax.nn.silu(_lin(x, p["g_proj"]["w"], low))
    return _lin(o * gate, p["wo"]["w"], low)


def _ffn(p, x, low):
    h = jax.nn.silu(_lin(x, p["w1"], low)) * _lin(x, p["w3"], low)
    return _lin(h, p["w2"], low)


@functools.partial(jax.jit, static_argnames=("block", "eps", "low"))
def _block(x, p, r, *, block, eps, low):
    """One residual block; ``p`` is the group's stacked (or shared) params,
    ``r`` the repeat to take (ignored for a shared block)."""
    block = _thaw(block)
    if not block.get("shared", False):
        p = jax.tree.map(lambda a: a[r], p)
    mixer = block["mixer"]
    h = _rms(x, p["ln1"], eps)
    if mixer["type"] == "attention":
        x = x + _attention(p["mixer"], h, mixer, low)
    elif mixer["kind"] == "mamba2":
        x = x + _mamba2(p["mixer"], h, mixer, eps, low)
    else:
        raise ValueError(f"no reference for mixer {mixer['kind']!r}")
    if block["ffn"]["kind"] == "dense":
        x = x + _ffn(p["ffn"], _rms(x, p["ln2"], eps), low)
    elif block["ffn"]["kind"] != "none":
        raise ValueError(f"no reference for ffn {block['ffn']['kind']!r}")
    return x


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _logits(x, read, final_norm, unembed, *, eps, low):
    h = jnp.take_along_axis(x, read[..., None], axis=1)      # (B, P, d)
    return _lin(_rms(h, final_norm, eps), unembed, low)      # (B, P, V)


def _freeze(obj):
    if isinstance(obj, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in obj.items()))
    if isinstance(obj, list):
        return ("__list__",) + tuple(_freeze(v) for v in obj)
    return obj


def _thaw(obj):
    if isinstance(obj, tuple) and obj and obj[0] == "__list__":
        return [_thaw(v) for v in obj[1:]]
    if isinstance(obj, tuple) and all(isinstance(e, tuple) and len(e) == 2
                                      for e in obj):
        return {k: _thaw(v) for k, v in obj}
    return obj


def logits(model: dict, params, tokens, read, *, low: bool = False):
    """Next-token logits (B, P, V) float32 at positions ``read`` (B, P) of
    the teacher-forced ``tokens`` (B, T); T a multiple of 64."""
    eps = float(model["norm_eps"])
    x = params["embed"][tokens].astype(jnp.float32)
    for gi, g in enumerate(model["groups"]):
        gp = params["groups"][gi]
        for r in range(int(g["repeats"])):
            for bi, name in enumerate(g["blocks"]):
                block = model["blocks"][name]
                p = (gp["shared"] if block.get("shared") else
                     gp["stacked"])[f"b{bi}"]
                x = _block(x, p, jnp.int32(r), block=_freeze(block),
                           eps=eps, low=low)
    return _logits(x, read, params["final_norm"], params["unembed"],
                   eps=eps, low=low)
