"""Chunked (gated) delta rule for TPU (Pallas): DeltaNet / GDN / KDA.

Recurrence (scalar per-head decay a_t, write strength beta_t, keys
L2-normalized by the caller):

    S_t = a_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = q_t S_t

Chunked via the WY representation. The per-chunk unit-lower-triangular system
(I + diag(beta) A) U = diag(beta) (V - K~ S0) is solved with the *Neumann
product* factorization: for N strictly lower triangular (nilpotent, N^C = 0),

    (I + N)^{-1} = prod_{i=0}^{log2(C)-1} (I + (-N)^{2^i})

i.e. log2(C) dense (C x C) matmuls on the MXU — a TPU-native substitute for
the warp-level forward substitution used by GPU implementations (see
DESIGN.md §3). All decay factors are exp(non-positive log-gamma differences),
so every scale is <= 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gla import decay_cumsum


def _neumann_unit_lower_inverse(n, chunk):
    """Inverse of (I + n) for strictly-lower-triangular n, via log2(C) matmuls."""
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye = (row == col).astype(jnp.float32)
    m = -n
    r = eye + m
    steps = max(1, (chunk - 1).bit_length())
    for _ in range(steps - 1):
        m = jax.lax.dot(m, m)
        r = r + jax.lax.dot(r, m)
    return r


def _delta_chunk_step(q, k, v, la_row, beta_row, S, chunk):
    """One WY-representation chunk of the recurrence: (o, new state), fp32.
    ``la_row``/``beta_row``: the chunk's (1, C) log-decay and beta rows."""
    csum, csum_row, row, col = decay_cumsum(la_row, chunk)
    gamma = jnp.exp(csum)                               # (C,1) <= 1
    # beta as a column, read off the diagonal (exact)
    beta = jnp.sum(jnp.where(col == row, beta_row, 0.0), axis=1,
                   keepdims=True)                       # (C, 1)

    strict = col < row
    incl = col <= row
    diff = csum - csum_row
    decay_strict = jnp.where(strict, jnp.exp(jnp.where(strict, diff, 0.0)), 0.0)
    decay_incl = jnp.where(incl, jnp.exp(jnp.where(incl, diff, 0.0)), 0.0)

    kkt = jax.lax.dot_general(k, k, (((1,), (1,)), ((), ())))   # (C, C)
    n = beta * (kkt * decay_strict)                     # diag(beta) A, strictly lower
    tinv = _neumann_unit_lower_inverse(n, chunk)        # (I + N)^-1

    rhs = beta * (v - jax.lax.dot(k * gamma, S))        # (C, dv)
    u = jax.lax.dot(tinv, rhs)                          # (C, dv)

    qkt = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
    o = jax.lax.dot(q * gamma, S) + jax.lax.dot(qkt * decay_incl, u)

    last = jnp.sum(jnp.where(col[:1] == chunk - 1, csum_row, 0.0), axis=1,
                   keepdims=True)                       # (1, 1) = csum[-1]
    kscale = jnp.exp(last - csum)
    S = jnp.exp(last) * S + jax.lax.dot_general(
        k * kscale, u, (((0,), (0,)), ((), ())))
    return o, S


def _delta_kernel(len_ref, q_ref, k_ref, v_ref, la_ref, b_ref, s0_ref, o_ref,
                  sT_ref, state, *, chunk, num_chunks, num_heads):
    """One (row-head, chunk) grid step.  With ``len_ref`` (the fused
    variant), rows past the row's valid length are neutralized in-VMEM
    (beta -> 0: no write, log_a -> 0: no decay, k -> 0) so the caller skips
    the full-tensor masking passes."""
    h = pl.program_id(0)
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state[...] = s0_ref[0].astype(jnp.float32)

    q = q_ref[0].astype(jnp.float32)                    # (C, dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)                    # (C, dv)
    la = la_ref[0, 0].astype(jnp.float32)               # (1, C)
    beta = b_ref[0, 0].astype(jnp.float32)              # (1, C)
    if len_ref is not None:
        length = len_ref[h // num_heads]
        pos = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        k = jnp.where(pos < length, k, 0.0)
        pos_row = c * chunk + jax.lax.broadcasted_iota(jnp.int32,
                                                       (1, chunk), 1)
        la = jnp.where(pos_row < length, la, 0.0)
        beta = jnp.where(pos_row < length, beta, 0.0)

    o, S = _delta_chunk_step(q, k, v, la, beta, state[...], chunk)
    state[...] = S
    o_ref[0] = o.astype(o_ref.dtype)

    @pl.when(c == num_chunks - 1)
    def _finish():
        sT_ref[0] = state[...]


def _delta_call(q, k, v, log_a, beta, lengths, initial_state, chunk,
                interpret):
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    if initial_state is None:
        initial_state = jnp.zeros((B, H, dk, dv), jnp.float32)

    chunk = min(chunk, max(S, 8))
    pad = (-S) % chunk
    if pad:
        # padded tokens: beta = 0 and log_a = 0 -> state passes through;
        # with lengths they also land at pos >= S >= lengths -> masked
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        log_a = jnp.pad(log_a, ((0, 0), (0, 0), (0, pad)))
        beta = jnp.pad(beta, ((0, 0), (0, 0), (0, pad)))
    Sp = S + pad
    nc = Sp // chunk

    qr = q.reshape(B * H, Sp, dk)
    kr = k.reshape(B * H, Sp, dk)
    vr = v.reshape(B * H, Sp, dv)
    # (1, chunk) rows per grid step, as in gla: TPU-legal for any chunk
    lar = log_a.reshape(B * H, nc, 1, chunk)
    br = beta.reshape(B * H, nc, 1, chunk)
    s0 = initial_state.reshape(B * H, dk, dv)
    fused = lengths is not None
    lens = (lengths.astype(jnp.int32).reshape(B) if fused
            else jnp.zeros((1,), jnp.int32))

    def kernel(len_ref, *refs):
        _delta_kernel(len_ref if fused else None, *refs, chunk=chunk,
                      num_chunks=nc, num_heads=H)

    row_spec = pl.BlockSpec((1, 1, 1, chunk), lambda h, c, ln: (h, c, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda h, c, ln: (h, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda h, c, ln: (h, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda h, c, ln: (h, c, 0)),
            row_spec,
            row_spec,
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
        name="delta",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sp, dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, dk, dv), jnp.float32),
        ],
        interpret=interpret,
    )(lens, qr, kr, vr, lar, br, s0)
    o = o.reshape(B, H, Sp, dv)[:, :, :S]
    return o, sT.reshape(B, H, dk, dv)


def delta_chunked(q, k, v, log_a, beta, initial_state=None, *,
                  chunk: int = 64, interpret: bool = False):
    """q,k: (B,H,S,dk); v: (B,H,S,dv); log_a, beta: (B,H,S).

    Returns (o: (B,H,S,dv), final_state: (B,H,dk,dv) float32).
    """
    return _delta_call(q, k, v, log_a, beta, None, initial_state, chunk,
                       interpret)


def delta_chunked_fused(q, k, v, log_a, beta, lengths, initial_state=None, *,
                        chunk: int = 64, interpret: bool = False):
    """``delta_chunked`` with per-row valid ``lengths: (B,)`` applied inside
    the kernel instead of by full-tensor ``jnp.where`` passes (the serving
    prefill path's padded-bucket masking).

    Returns (o: (B,H,S,dv), final_state: (B,H,dk,dv) float32). Output rows
    at positions >= lengths[b] are unspecified (the engine discards them).
    """
    return _delta_call(q, k, v, log_a, beta, lengths, initial_state, chunk,
                       interpret)
