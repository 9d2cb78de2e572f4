"""Flash-decode for TPU (Pallas): one query token vs a long KV cache.

Decode is memory-bandwidth bound, so the kernel reads each live KV tile
from HBM once.  The grid is ``(slot, KV tile)``: one step holds a
``(Hkv, block_k, D)`` tile of every KV head of a slot and serves all
``group = Hq / Hkv`` query heads of each of them, with running-softmax
statistics per query head in VMEM scratch.  Per-slot cache lengths ride
in as a scalar-prefetch operand (SMEM, read by slot index: a (1, 1) block
of a (B, 1) array breaks the TPU's (8, 128) block rule).  The K/V index
maps clamp the tile index to the slot's live range,
``[(len - window) // block_k, (len - 1) // block_k]``, so the pipeline
sees an unchanged block for the dead steps before and after it and issues
no copy for them; the body skips their compute.  Sliding-window archs
mask keys below ``length - window``, so SWA decode touches O(window)
bytes.

``block_k`` follows from the shapes (``pick_block_k``): the largest power
of two whose K+V tile over all KV heads fits ``TILE_BYTES``.
``tiles_fetched`` counts, on the host, the tiles a call copies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
# one grid step's K + V tile over all KV heads; double-buffered that is
# 4 MiB of the 16 MiB default scoped VMEM
TILE_BYTES = 2 << 20
MIN_BLOCK_K = 16


def pick_block_k(S: int, kv_heads: int, dk: int, dv: int,
                 itemsize: int) -> int:
    """Keys per tile: the largest power of two (at least ``MIN_BLOCK_K``)
    whose K + V rows of all ``kv_heads`` fit ``TILE_BYTES``, or ``S``
    itself when the whole capacity fits one tile."""
    rows = TILE_BYTES // (kv_heads * (dk + dv) * itemsize)
    bk = MIN_BLOCK_K
    while bk * 2 <= rows:
        bk *= 2
    return S if S <= bk else bk


def _live_tiles(lengths, block_k, window, xp=jnp):
    """First and last tile index a slot of each length reads (both
    clamped at 0: a slot with no key still holds its first tile)."""
    hi = xp.maximum(lengths - 1, 0) // block_k
    lo = xp.maximum(lengths - window, 0) // block_k if window > 0 else 0
    return lo, hi


def tiles_fetched(lengths, S: int, window: int = 0, *, kv_heads: int,
                  dk: int, dv: int, itemsize: int):
    """(tiles copied from HBM, tiles of the whole capacity) by one call
    of ``decode_attention`` over caches of ``S`` keys at these widths:
    per slot its live range of ``block_k``-key tiles, at least one.
    Host numpy; ``lengths`` is the ``(B,)`` key counts of the call."""
    bk = pick_block_k(S, kv_heads, dk, dv, itemsize)
    lens = np.asarray(lengths)
    lo, hi = _live_tiles(lens, bk, window, np)
    return int((hi - lo).sum()) + lens.size, lens.size * -(-S // bk)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale, window, block_k, num_kv_blocks, kv_heads,
                   group, dot_dtype):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    k_start = j * block_k
    live = k_start < length
    if window > 0:
        live &= (k_start + block_k) > (length - window)

    @pl.when(live)
    def _compute():
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (group, block_k), 1)
        mask = kpos < length
        if window > 0:
            mask &= kpos >= (length - window)
        for h in range(kv_heads):
            q = q_ref[h * group:(h + 1) * group].astype(dot_dtype)  # (g, Dk)
            k = k_ref[h].astype(dot_dtype)                          # (bk, Dk)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale        # (g, bk)
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h]                                       # (g, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            safe_m = jnp.where(m_new == NEG_INF, 0.0, m_new)
            corr = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - safe_m))
            p = jnp.where(s == NEG_INF, 0.0, jnp.exp(s - safe_m))
            l_ref[h] = corr * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[h] = m_new
            v = v_ref[h].astype(jnp.float32)                        # (bk, Dv)
            acc_ref[h] = corr * acc_ref[h] + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)

    @pl.when(j == num_kv_blocks - 1)
    def _finish():
        for h in range(kv_heads):
            l = l_ref[h]
            o = (acc_ref[h] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
            for g in range(group):           # (1, Dv) rows: Dv may not be
                o_ref[h * group + g] = o[g:g + 1]   # a multiple of 128


def decode_attention(q, k_cache, v_cache, lengths, *, window: int = 0,
                     scale: float | None = None, block_k: int | None = None,
                     interpret: bool = False):
    """q: (B, Hq, Dk); caches: (B, Hkv, S, Dk / Dv); lengths: (B,)
    -> (B, Hq, Dv).

    ``block_k`` overrides ``pick_block_k`` (tests use it to get many tiles
    at small shapes)."""
    B, Hq, D = q.shape
    _, Hkv, S, _ = k_cache.shape
    Dv = v_cache.shape[-1]
    assert Hq % Hkv == 0
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if block_k is None:
        block_k = pick_block_k(S, Hkv, D, Dv, k_cache.dtype.itemsize)
    block_k = min(block_k, S)

    pk = (-S) % block_k
    kp = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else k_cache
    vp = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else v_cache
    Sp = S + pk
    nk = Sp // block_k

    # q and o keep a unit row axis, (B·Hq, 1, D); q's block squeezes it
    qr = q.reshape(B * Hq, 1, D)
    kr = kp.reshape(B * Hkv, Sp, D)
    vr = vp.reshape(B * Hkv, Sp, Dv)
    lens = lengths.astype(jnp.int32).reshape(B)

    def kv_map(b, j, lens_ref):
        lo, hi = _live_tiles(lens_ref[b], block_k, window)
        return (b, jnp.minimum(jnp.maximum(j, lo), hi), 0)

    def row_map(b, j, lens_ref):
        return (b, 0, 0)

    dot_dtype = jnp.promote_types(q.dtype, k_cache.dtype)
    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               block_k=block_k, num_kv_blocks=nk,
                               kv_heads=Hkv, group=group, dot_dtype=dot_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((Hq, None, D), row_map),
            pl.BlockSpec((Hkv, block_k, D), kv_map),
            pl.BlockSpec((Hkv, block_k, Dv), kv_map),
        ],
        out_specs=pl.BlockSpec((Hq, 1, Dv), row_map),
        scratch_shapes=[
            pltpu.VMEM((Hkv, group, Dv), jnp.float32),
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
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
