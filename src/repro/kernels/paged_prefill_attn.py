"""Paged flash-prefill for TPU (Pallas): suffix chunks over block tables.

A prefix-hit suffix prefill attends each chunk's queries over (a) the
device-resident cached prefix — pool pages ``(Hkv, P, T, D)`` addressed
through the request's block table — and (b) the dense suffix keys
accumulated so far (whose last C rows are the chunk's own, causally
masked). The pre-kernel path gathered the table's pages into a dense
``(B, c, Hkv, D)`` prior operand first; here the table rides in as a
scalar-prefetch operand so each grid step DMAs one (T x D) KV tile
straight from its pooled page — the cached prefix is never materialized
outside the pool.

Grid ``(B * Hq, N + 1)``: steps j < N stream the N prior pages (all fully
visible — every prior position precedes every query), step j == N streams
the dense suffix with the causal mask and normalizes. Same running-softmax
core as ``paged_decode_attn.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _accum(s, v, acc_ref, m_ref, l_ref):
    """Streaming-softmax accumulation of scores s: (C, L) against v: (L, Dv)."""
    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    safe_m = jnp.where(m_new == NEG_INF, 0.0, m_new)
    corr = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - safe_m))
    p = jnp.where(s == NEG_INF, 0.0, jnp.exp(s - safe_m))
    l_ref[...] = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = corr * acc_ref[...] + jax.lax.dot(p, v)


def _paged_prefill_kernel(tbl_ref, q_ref, kp_ref, vp_ref, ks_ref, vs_ref,
                          o_ref, acc_ref, m_ref, l_ref, *, scale, num_pages,
                          chunk_q, suffix_len):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale              # (C, D)

    @pl.when(j < num_pages)
    def _page():
        k = kp_ref[0, 0].astype(jnp.float32)                 # (T, D)
        v = vp_ref[0, 0].astype(jnp.float32)
        # prior pages: every position < every query position -> no mask
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        _accum(s, v, acc_ref, m_ref, l_ref)

    @pl.when(j == num_pages)
    def _suffix():
        k = ks_ref[0, 0].astype(jnp.float32)                 # (Ssuf, D)
        v = vs_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        # query row i sits at suffix position (suffix_len - chunk_q) + i
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col <= (suffix_len - chunk_q) + row, s, NEG_INF)
        _accum(s, v, acc_ref, m_ref, l_ref)

        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_prefill_attention(q, k_pages, v_pages, tables, k_suf, v_suf, *,
                            scale: float | None = None,
                            interpret: bool = False):
    """q: (B, Hq, C, D); pages: (Hkv, P, T, D); tables: (B, N) int32;
    k_suf/v_suf: (B, Hkv, Ssuf, D). Returns (B, Hq, C, Dv)."""
    B, Hq, C, D = q.shape
    Hkv, P, T, _ = k_pages.shape
    Dv = v_pages.shape[-1]
    Ssuf = k_suf.shape[2]
    assert Hq % Hkv == 0
    assert Ssuf >= C, (Ssuf, C)
    group = Hq // Hkv
    N = tables.shape[1]
    assert tables.shape == (B, N) and N >= 1
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    qr = q.reshape(B * Hq, 1, C, D)
    tbl = tables.astype(jnp.int32)

    def page_map(h, j, tbl_ref):
        # j == N (the suffix step) clamps to a dummy page; pl.when skips it
        return ((h % Hq) // group, tbl_ref[h // Hq, jnp.minimum(j, N - 1)],
                0, 0)

    def suf_map(h, j, tbl_ref):
        return (h // Hq, (h % Hq) // group, 0, 0)

    kernel = functools.partial(
        _paged_prefill_kernel, scale=scale, num_pages=N, chunk_q=C,
        suffix_len=Ssuf)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * Hq, N + 1),
        in_specs=[
            pl.BlockSpec((1, 1, C, D), lambda h, j, tbl_ref: (h, 0, 0, 0)),
            pl.BlockSpec((1, 1, T, D), page_map),
            pl.BlockSpec((1, 1, T, Dv), page_map),
            pl.BlockSpec((1, 1, Ssuf, D), suf_map),
            pl.BlockSpec((1, 1, Ssuf, Dv), suf_map),
        ],
        out_specs=pl.BlockSpec((1, 1, C, Dv),
                               lambda h, j, tbl_ref: (h, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((C, Dv), jnp.float32),
            pltpu.VMEM((C, 1), jnp.float32),
            pltpu.VMEM((C, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_prefill_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hq, 1, C, Dv), q.dtype),
        interpret=interpret,
    )(tbl, qr, k_pages, v_pages, k_suf, v_suf)
    return out.reshape(B, Hq, C, Dv)
