"""Flash attention for TPU (Pallas): causal / GQA / sliding-window prefill.

TPU-native design: the KV axis is the innermost *sequential* grid dimension,
so the running-softmax statistics (m, l) and the output accumulator live in
VMEM scratch across KV steps — the MXU sees (block_q x D) @ (D x block_k)
and (block_q x block_k) @ (block_k x D) matmuls with hardware-aligned tiles.
Fully-masked KV blocks are skipped with ``pl.when`` (causal + window
block-level bounds), which is where SWA's linear cost comes from.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, causal, window, kv_valid, q_offset,
                  block_q, block_k, num_kv_blocks):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    i = pl.program_id(1)
    q_start = i * block_q + q_offset          # global position of q row 0
    k_start = j * block_k

    # Block-level skip: block is live unless fully masked.
    live = k_start < kv_valid
    if causal:
        live &= k_start <= q_start + block_q - 1
    if window > 0:
        live &= (k_start + block_k) > (q_start - window + 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale        # (bq, D)
        k = k_ref[0].astype(jnp.float32)                # (bk, D)
        v = v_ref[0].astype(jnp.float32)                # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < kv_valid
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                              # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked-so-far rows (m == -inf)
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


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D) -> (B, Hq, Sq, D)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    Dv = v.shape[-1]
    assert Hq % Hkv == 0
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if q_offset == 0 and causal and Sq != Sk:
        q_offset = Sk - Sq

    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else v

    Sqp, Skp = Sq + pq, Sk + pk
    qr = qp.reshape(B * Hq, Sqp, D)
    kr = kp.reshape(B * Hkv, Skp, D)
    vr = vp.reshape(B * Hkv, Skp, Dv)
    nq, nk = Sqp // block_q, Skp // block_k

    def q_map(h, i, j):
        return (h, i, 0)

    def kv_map(h, i, j):
        return ((h // Hq) * Hkv + (h % Hq) // group, j, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        kv_valid=Sk, q_offset=q_offset, block_q=block_q, block_k=block_k,
        num_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        name="flash_attn",
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, Dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dv), q_map),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sqp, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    out = out.reshape(B, Hq, Sqp, Dv)
    return out[:, :, :Sq] if pq else out
