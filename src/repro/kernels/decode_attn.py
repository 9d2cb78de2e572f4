"""Flash-decode for TPU (Pallas): one query token vs a long KV cache.

Decode is memory-bandwidth bound: the kernel streams (block_k x D) KV tiles
HBM->VMEM once, with running-softmax statistics in VMEM scratch. Per-request
cache lengths ride in as a scalar-prefetch operand (SMEM, read by row index
in the body: a (1, 1) block of a (B, 1) array breaks the TPU's (8, 128)
block rule). Sliding-window archs mask keys below ``length - window`` so
SWA decode touches O(window) bytes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, scale, window, block_k, num_kv_blocks, num_q_heads):
    h = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[h // num_q_heads]
    k_start = j * block_k
    live = k_start < length
    if window > 0:
        live &= (k_start + block_k) > (length - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale         # (1, D)
        k = k_ref[0].astype(jnp.float32)                 # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (1, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < length
        if window > 0:
            mask &= kpos >= (length - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        safe_m = jnp.where(m_new == NEG_INF, 0.0, m_new)
        corr = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - safe_m))
        p = jnp.where(s == NEG_INF, 0.0, jnp.exp(s - safe_m))
        l_ref[...] = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = corr * acc_ref[...] + jax.lax.dot(p, v)

    @pl.when(j == num_kv_blocks - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, window: int = 0,
                     scale: float | None = None, block_k: int = 512,
                     interpret: bool = False):
    """q: (B, Hq, D); caches: (B, Hkv, S, D); lengths: (B,) -> (B, Hq, D)."""
    B, Hq, D = q.shape
    _, Hkv, S, _ = k_cache.shape
    Dv = v_cache.shape[-1]
    assert Hq % Hkv == 0
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    block_k = min(block_k, max(S, 8))
    pk = (-S) % block_k
    kp = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else k_cache
    vp = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else v_cache
    Sp = S + pk
    nk = Sp // block_k

    qr = q.reshape(B * Hq, 1, D)
    kr = kp.reshape(B * Hkv, Sp, D)
    vr = vp.reshape(B * Hkv, Sp, Dv)
    lens = lengths.astype(jnp.int32).reshape(B)

    def kv_map(h, j, lens_ref):
        return ((h // Hq) * Hkv + (h % Hq) // group, j, 0)

    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               block_k=block_k, num_kv_blocks=nk,
                               num_q_heads=Hq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * Hq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda h, j, lens_ref: (h, 0, 0)),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, Dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, Dv), lambda h, j, lens_ref: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, Dv), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="decode_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hq, 1, Dv), q.dtype),
        interpret=interpret,
    )(lens, qr, kr, vr)
    return out.reshape(B, Hq, Dv)
