"""Mamba2 (SSD) linear attention.

q/k/v projections, a depthwise causal conv of width K over [q, k, v] and
SiLU, k scaled by 1/sqrt(dk), per-head decay
a_t = exp(-exp(A_log) * softplus(x W_a + dt_bias)), the recurrence
S_t = a_t S_{t-1} + k_t^T v_t, o_t = q_t S_t + D v_t, a per-head RMSNorm of
o, the gate SiLU(x W_g) and the output projection.  The recurrence is
evaluated exactly, a chunk of 64 steps at a time (intra-chunk decayed
products plus the carried state), all in float32.  FLOPs per token beside
the weights: the recurrence, 4 * heads * dk * dv, and the conv,
2 * K * channels.
"""
import jax
import jax.numpy as jnp

from bench.layers import _lin, _mm

SSD_CHUNK = 64


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


def forward(p, x, spec, *, eps, low):
    B, T, _ = x.shape
    H, dk, dv, K = (spec["heads"], spec["key_dim"], spec["value_dim"],
                    spec["conv_kernel"])
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


def matmul_params(spec, d_model):
    d, H, dk, dv = d_model, spec["heads"], spec["key_dim"], spec["value_dim"]
    n = d * H * (2 * dk + dv)               # q, k, v
    n += d * H + d * H * dv + H * dv * d    # decay, gate, output
    return n


def state_flops(spec):
    H, dk, dv = spec["heads"], spec["key_dim"], spec["value_dim"]
    return 4.0 * H * dk * dv + 2.0 * spec["conv_kernel"] * H * (2 * dk + dv)
