"""Paged flash-decode for TPU (Pallas): block-table KV gather.

Same running-softmax core as ``decode_attn.py``, but the KV lives in a
shared page pool ``(Hkv, P, T, D)`` instead of per-request dense buffers:
logical page ``j`` of request ``b`` is physical page ``tables[b, j]``. The
table and per-request lengths ride in as scalar-prefetch operands
(``PrefetchScalarGridSpec``) so the page indirection happens in the index
map — each grid step DMAs exactly one (T x D) KV tile straight from its
pooled page, no gather materialization. Pages at positions >= length may be
sink/garbage pages; the length mask keeps them out of the softmax.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _paged_decode_kernel(len_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, scale, window, page_tokens,
                         num_pages, num_q_heads):
    h = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[h // num_q_heads]
    k_start = j * page_tokens
    live = k_start < length
    if window > 0:
        live &= (k_start + page_tokens) > (length - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale         # (1, D)
        k = k_ref[0, 0].astype(jnp.float32)              # (T, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (1, T)
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

    @pl.when(j == num_pages - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, tables, lengths, *,
                           window: int = 0, scale: float | None = None,
                           interpret: bool = False):
    """q: (B, Hq, D); pages: (Hkv, P, T, D); tables: (B, N) int32 ->
    (B, Hq, Dv)."""
    B, Hq, D = q.shape
    Hkv, P, T, _ = k_pages.shape
    Dv = v_pages.shape[-1]
    assert Hq % Hkv == 0
    group = Hq // Hkv
    N = tables.shape[1]
    assert tables.shape == (B, N) and N >= 1
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    qr = q.reshape(B * Hq, 1, D)
    lens = lengths.astype(jnp.int32).reshape(B)
    tbl = tables.astype(jnp.int32)

    def kv_map(h, j, lens_ref, tbl_ref):
        return ((h % Hq) // group, tbl_ref[h // Hq, j], 0, 0)

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, window=window, page_tokens=T,
        num_pages=N, num_q_heads=Hq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * Hq, N),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda h, j, lens_ref, tbl_ref: (h, 0, 0)),
            pl.BlockSpec((1, 1, T, D), kv_map),
            pl.BlockSpec((1, 1, T, Dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, Dv),
                               lambda h, j, lens_ref, tbl_ref: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, Dv), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hq, 1, Dv), q.dtype),
        interpret=interpret,
    )(lens, tbl, qr, k_pages, v_pages)
    return out.reshape(B, Hq, Dv)
