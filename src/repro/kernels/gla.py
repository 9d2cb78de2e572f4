"""Chunked gated linear attention for TPU (Pallas).

Covers Mamba2/SSD (scalar per-head decay), Lightning/simple linear attention
(decay = 1), GLA, and mLSTM (via the caller augmenting v with a normalizer
column). Recurrence:

    S_t = a_t * S_{t-1} + k_t v_t^T ,   o_t = q_t S_t ,   a_t = exp(log_a_t)

TPU-native chunking: the chunk axis is a sequential grid dimension; the
(dk x dv) fp32 state is carried in VMEM scratch. All decay factors are
expressed as exp(differences of log-decay cumsums) with non-positive
exponents, so every scaling factor is <= 1 (numerically safe for strong
decay — no 1/gamma anywhere).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def decay_cumsum(la_row, chunk):
    """Inclusive cumsum of a chunk's (1, C) log-decay row, returned as a
    (C, 1) column and a (1, C) row, plus the (C, C) row/col position iotas.

    Mosaic has no cumsum, so the column is a masked lane reduction, and the
    row is read off the column's diagonal (adding exact zeros) — both forms
    hold bit-identical values, so ``diff`` below is exactly 0 on the
    diagonal."""
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    csum_col = jnp.sum(jnp.where(col <= row, la_row, 0.0), axis=1,
                       keepdims=True)                   # (C, 1)
    csum_row = jnp.sum(jnp.where(col == row, csum_col, 0.0), axis=0,
                       keepdims=True)                   # (1, C)
    return csum_col, csum_row, row, col


def _gla_chunk_step(q, k, v, la_row, S, chunk):
    """One chunk of the recurrence: returns (o, new state), all fp32."""
    csum, csum_row, row, col = decay_cumsum(la_row, chunk)
    gamma = jnp.exp(csum)                               # (C, 1), <= 1

    # intra-chunk: A[t,s] = (q_t . k_s) * exp(csum_t - csum_s), s <= t
    diff = csum - csum_row                              # <= 0 on lower tri
    incl = col <= row
    decay = jnp.where(incl, jnp.exp(jnp.where(incl, diff, 0.0)), 0.0)
    A = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * decay
    o = jax.lax.dot(A, v) + jax.lax.dot(q * gamma, S)

    # state update: S <- gamma_C * S + sum_s (gamma_C / gamma_s) k_s v_s^T
    last = jnp.sum(jnp.where(col[:1] == chunk - 1, csum_row, 0.0), axis=1,
                   keepdims=True)                       # (1, 1) = csum[-1]
    kscale = jnp.exp(last - csum)                       # (C, 1), <= 1
    S = jnp.exp(last) * S + jax.lax.dot_general(
        k * kscale, v, (((0,), (0,)), ((), ())))
    return o, S


def _gla_kernel(len_ref, q_ref, k_ref, v_ref, la_ref, s0_ref, o_ref, sT_ref,
                state, *, chunk, num_chunks, num_heads):
    """One (row-head, chunk) grid step.  With ``len_ref`` (the fused
    variant), rows at positions >= the row's valid length are neutralized
    in-VMEM (k -> 0: no state write; log_a -> 0: no decay) so the caller
    skips the full-tensor masking passes over k/log_a."""
    h = pl.program_id(0)
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state[...] = s0_ref[0].astype(jnp.float32)

    q = q_ref[0].astype(jnp.float32)                    # (C, dk)
    k = k_ref[0].astype(jnp.float32)                    # (C, dk)
    v = v_ref[0].astype(jnp.float32)                    # (C, dv)
    la = la_ref[0, 0].astype(jnp.float32)               # (1, C)
    if len_ref is not None:
        length = len_ref[h // num_heads]
        pos = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        k = jnp.where(pos < length, k, 0.0)
        pos_row = c * chunk + jax.lax.broadcasted_iota(jnp.int32,
                                                       (1, chunk), 1)
        la = jnp.where(pos_row < length, la, 0.0)

    o, S = _gla_chunk_step(q, k, v, la, state[...], chunk)
    state[...] = S
    o_ref[0] = o.astype(o_ref.dtype)

    @pl.when(c == num_chunks - 1)
    def _finish():
        sT_ref[0] = state[...]


def _gla_call(q, k, v, log_a, lengths, initial_state, chunk, interpret):
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    if initial_state is None:
        initial_state = jnp.zeros((B, H, dk, dv), jnp.float32)

    chunk = min(chunk, max(S, 8))
    pad = (-S) % chunk
    if pad:
        # padded tokens: k = 0 (no state write), log_a = 0 (no decay); with
        # lengths they also land at pos >= S >= lengths -> masked in-kernel
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        log_a = jnp.pad(log_a, ((0, 0), (0, 0), (0, pad)))
    Sp = S + pad
    nc = Sp // chunk

    qr = q.reshape(B * H, Sp, dk)
    kr = k.reshape(B * H, Sp, dk)
    vr = v.reshape(B * H, Sp, dv)
    # one (1, chunk) row per grid step: the block's last two dims equal the
    # array's, which the TPU's (8, 128) block rule accepts for any chunk
    lar = log_a.reshape(B * H, nc, 1, chunk)
    s0 = initial_state.reshape(B * H, dk, dv)
    fused = lengths is not None
    lens = (lengths.astype(jnp.int32).reshape(B) if fused
            else jnp.zeros((1,), jnp.int32))

    def kernel(len_ref, *refs):
        _gla_kernel(len_ref if fused else None, *refs, chunk=chunk,
                    num_chunks=nc, num_heads=H)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda h, c, ln: (h, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda h, c, ln: (h, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda h, c, ln: (h, c, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda h, c, ln: (h, c, 0, 0)),
            pl.BlockSpec((1, dk, dv), lambda h, c, ln: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda h, c, ln: (h, c, 0)),
            pl.BlockSpec((1, dk, dv), lambda h, c, ln: (h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
    )
    o, sT = pl.pallas_call(
        kernel,
        name="gla",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sp, dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, dk, dv), jnp.float32),
        ],
        interpret=interpret,
    )(lens, qr, kr, vr, lar, s0)
    o = o.reshape(B, H, Sp, dv)[:, :, :S]
    return o, sT.reshape(B, H, dk, dv)


def gla_chunked(q, k, v, log_a, initial_state=None, *, chunk: int = 64,
                interpret: bool = False):
    """q,k: (B,H,S,dk); v: (B,H,S,dv); log_a: (B,H,S) (<=0).

    Returns (o: (B,H,S,dv), final_state: (B,H,dk,dv) float32).
    """
    return _gla_call(q, k, v, log_a, None, initial_state, chunk, interpret)


def gla_chunked_fused(q, k, v, log_a, lengths, initial_state=None, *,
                      chunk: int = 64, interpret: bool = False):
    """``gla_chunked`` with per-row valid ``lengths: (B,)`` applied inside
    the kernel instead of by full-tensor ``jnp.where`` passes on the host
    program (the serving prefill path's padded-bucket masking).

    Returns (o: (B,H,S,dv), final_state: (B,H,dk,dv) float32). Output rows
    at positions >= lengths[b] are unspecified (the engine discards them).
    """
    return _gla_call(q, k, v, log_a, lengths, initial_state, chunk,
                     interpret)
