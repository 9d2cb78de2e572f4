"""Public kernel ops: jit-friendly dispatch wrappers.

Each op:
  * runs its compiled Pallas kernel on TPU — there is no other TPU path
    unless the call site passes ``use_kernel=False``; on CPU models lower
    the jnp paths, and the kernel tests run the kernel body in
    ``interpret=True`` mode (see ``_on_cpu_lowering``);
  * is differentiable via ``jax.custom_vjp`` whose backward pass is the VJP
    of the pure-jnp oracle with recomputation (flash-attention-style: store
    only the inputs, recompute the forward in the backward). Gradients are
    therefore oracle-exact while the forward stays on the kernel.
  * can be forced onto the oracle with ``use_kernel=False``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import decode_attn as _decode
from repro.kernels import paged_decode_attn as _paged_decode
from repro.kernels import paged_prefill_attn as _paged_prefill
from repro.kernels import delta as _delta
from repro.kernels import flash_attn as _flash
from repro.kernels import gla as _gla
from repro.kernels import quantize as _quant
from repro.kernels import ref

# lowerable memory-efficient paths (used when the TPU kernel is unavailable
# -- CPU tests and the dry-run -- and as the kernels' backward recompute)
from repro.models import chunked_attention as chk

# below this many KV tokens the plain quadratic oracle is cheapest
SMALL_SEQ = 1024


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _flash_vjp(causal, window, scale, q_offset, block_q, block_k, interpret):
    @jax.custom_vjp
    def op(q, k, v):
        return _flash.flash_attention(
            q, k, v, causal=causal, window=window, scale=scale,
            q_offset=q_offset, block_q=block_q, block_k=block_k,
            interpret=interpret)

    def fwd(q, k, v):
        return op(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        # memory-safe recompute backward (flash-style)
        _, vjp = jax.vjp(
            lambda q, k, v: _attention_jnp(
                q, k, v, causal=causal, window=window, scale=scale,
                q_offset=q_offset),
            q, k, v)
        return vjp(g)

    op.defvjp(fwd, bwd)
    return op


def _attention_jnp(q, k, v, *, causal=True, window=0, scale=None,
                   q_offset=0):
    """Shape-adaptive lowerable path: banded (SWA) / checkpointed-MEA
    (long full attention) / quadratic oracle (short)."""
    from repro.models.perf_flags import FLAGS, shard_hint
    if FLAGS.shard_attention:
        q = shard_hint(q, ("pod", "data"), "model", None, None)
        k = shard_hint(k, ("pod", "data"),
                       "model" if k.shape[1] % 16 == 0 else None, None, None)
        v = shard_hint(v, ("pod", "data"),
                       "model" if v.shape[1] % 16 == 0 else None, None, None)
    Sk = k.shape[2]
    if Sk <= SMALL_SEQ:
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       scale=scale, q_offset=q_offset)
    if (window > 0 and causal and q.shape[2] == Sk
            and Sk >= 2 * window):
        return chk.swa_banded(q, k, v, window=window, scale=scale)
    return chk.mea_attention(q, k, v, causal=causal, window=window,
                             scale=scale, q_offset=q_offset)


def attention(q, k, v, *, causal=True, window=0, scale=None, q_offset=0,
              block_q=128, block_k=128, use_kernel=True):
    """Full attention (GQA/MQA/MHA/SWA). q:(B,Hq,S,D) k,v:(B,Hkv,S,D)."""
    if not use_kernel or _on_cpu_lowering(k.shape[2]):
        return _attention_jnp(q, k, v, causal=causal, window=window,
                              scale=scale, q_offset=q_offset)
    op = _flash_vjp(causal, window, scale, q_offset, block_q, block_k,
                    _on_cpu())
    return op(q, k, v)


# tests set this to exercise the ops->Pallas dispatch on CPU explicitly
FORCE_KERNEL_ON_CPU = False


def _on_cpu_lowering(seq: int) -> bool:
    """On CPU the jnp paths are used for ALL model lowering: interpret-mode
    Pallas executes the grid as a Python-semantics loop whose HLO cost
    profile is meaningless (and seq-dependent dispatch would make the cost
    probes measure different programs at different probe points). The
    kernels are TPU-target; on CPU they are validated by the dedicated
    kernel tests (interpret=True) and via FORCE_KERNEL_ON_CPU."""
    return _on_cpu() and not FORCE_KERNEL_ON_CPU


# ---------------------------------------------------------------------------
# gated linear attention (Mamba2 / GLA / Lightning / mLSTM)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _gla_vjp(chunk, interpret):
    @jax.custom_vjp
    def op(q, k, v, log_a, s0):
        return _gla.gla_chunked(q, k, v, log_a, s0, chunk=chunk,
                                interpret=interpret)

    def fwd(q, k, v, log_a, s0):
        return op(q, k, v, log_a, s0), (q, k, v, log_a, s0)

    def bwd(res, g):
        q, k, v, log_a, s0 = res
        _, vjp = jax.vjp(lambda *a: chk.gla_chunked_jnp(*a), q, k, v, log_a,
                         s0)
        return vjp(g)

    op.defvjp(fwd, bwd)
    return op


def _mask_padded(lengths, S, log_a, k, beta=None):
    """Padded-row neutralization for right-padded bucket batches: decay -> 1
    (log_a = 0), key/beta -> 0 past each row's valid length — EXACTLY the
    masking the fused kernels apply in-VMEM, so both dispatch targets of a
    ``lengths=`` call compute the same state."""
    mask = jnp.arange(S)[None, :] < lengths[:, None]         # (B, S)
    log_a = jnp.where(mask[:, None, :], log_a, 0.0)
    k = jnp.where(mask[:, None, :, None], k, jnp.zeros((), k.dtype))
    if beta is None:
        return log_a, k
    return log_a, k, jnp.where(mask[:, None, :], beta, 0.0)


def gla(q, k, v, log_a, initial_state=None, *, lengths=None, chunk=64,
        use_kernel=True):
    """Gated linear attention. Returns (o, final_state).

    ``lengths`` (B,): valid token counts for right-padded batches.  The
    kernel path fuses the padded-row masking (decay -> 1, k -> 0) into the
    chunked-state kernel; the jnp path applies the identical ``jnp.where``
    masking before the chunked scan."""
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    if initial_state is None:
        initial_state = jnp.zeros((B, H, dk, dv), jnp.float32)
    if lengths is None:
        if not use_kernel or _on_cpu_lowering(S):
            return chk.gla_chunked_jnp(q, k, v, log_a, initial_state,
                                       chunk=chunk)
        return _gla_vjp(chunk, _on_cpu())(q, k, v, log_a, initial_state)
    lengths = jnp.asarray(lengths, jnp.int32)
    if not use_kernel or _on_cpu_lowering(S):
        log_a, k = _mask_padded(lengths, S, log_a, k)
        return chk.gla_chunked_jnp(q, k, v, log_a, initial_state, chunk=chunk)
    interpret = _on_cpu()

    @jax.custom_vjp
    def op(q, k, v, log_a, s0):
        return _gla.gla_chunked_fused(q, k, v, log_a, lengths, s0,
                                      chunk=chunk, interpret=interpret)

    def fwd(q, k, v, log_a, s0):
        return op(q, k, v, log_a, s0), (q, k, v, log_a, s0)

    def bwd(res, g):
        q, k, v, log_a, s0 = res

        def oracle(q, k, v, log_a, s0):
            la, km = _mask_padded(lengths, S, log_a, k)
            return chk.gla_chunked_jnp(q, km, v, la, s0, chunk=chunk)

        _, vjp = jax.vjp(oracle, q, k, v, log_a, s0)
        return vjp(g)

    op.defvjp(fwd, bwd)
    return op(q, k, v, log_a, initial_state)


# ---------------------------------------------------------------------------
# (gated) delta rule (DeltaNet / GDN / KDA)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _delta_vjp(chunk, interpret):
    @jax.custom_vjp
    def op(q, k, v, log_a, beta, s0):
        return _delta.delta_chunked(q, k, v, log_a, beta, s0, chunk=chunk,
                                    interpret=interpret)

    def fwd(q, k, v, log_a, beta, s0):
        return op(q, k, v, log_a, beta, s0), (q, k, v, log_a, beta, s0)

    def bwd(res, g):
        q, k, v, log_a, beta, s0 = res
        _, vjp = jax.vjp(lambda *a: chk.delta_chunked_jnp(*a), q, k, v,
                         log_a, beta, s0)
        return vjp(g)

    op.defvjp(fwd, bwd)
    return op


def delta(q, k, v, log_a, beta, initial_state=None, *, lengths=None,
          chunk=64, use_kernel=True):
    """Gated delta rule. Returns (o, final_state).

    ``lengths`` as in :func:`gla`: the kernel path fuses the padded-row
    masking (decay -> 1, k/beta -> 0) into the chunked-state kernel."""
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    if initial_state is None:
        initial_state = jnp.zeros((B, H, dk, dv), jnp.float32)
    if lengths is None:
        if not use_kernel or _on_cpu_lowering(S):
            return chk.delta_chunked_jnp(q, k, v, log_a, beta, initial_state,
                                         chunk=chunk)
        return _delta_vjp(chunk, _on_cpu())(q, k, v, log_a, beta,
                                            initial_state)
    lengths = jnp.asarray(lengths, jnp.int32)
    if not use_kernel or _on_cpu_lowering(S):
        log_a, k, beta = _mask_padded(lengths, S, log_a, k, beta)
        return chk.delta_chunked_jnp(q, k, v, log_a, beta, initial_state,
                                     chunk=chunk)
    interpret = _on_cpu()

    @jax.custom_vjp
    def op(q, k, v, log_a, beta, s0):
        return _delta.delta_chunked_fused(q, k, v, log_a, beta, lengths, s0,
                                          chunk=chunk, interpret=interpret)

    def fwd(q, k, v, log_a, beta, s0):
        return op(q, k, v, log_a, beta, s0), (q, k, v, log_a, beta, s0)

    def bwd(res, g):
        q, k, v, log_a, beta, s0 = res

        def oracle(q, k, v, log_a, beta, s0):
            la, km, b = _mask_padded(lengths, S, log_a, k, beta)
            return chk.delta_chunked_jnp(q, km, v, la, b, s0, chunk=chunk)

        _, vjp = jax.vjp(oracle, q, k, v, log_a, beta, s0)
        return vjp(g)

    op.defvjp(fwd, bwd)
    return op(q, k, v, log_a, beta, initial_state)


# ---------------------------------------------------------------------------
# decode attention (no grad path needed — serving only)
# ---------------------------------------------------------------------------


def decode_attention(q, k_cache, v_cache, lengths, *, window=0, scale=None,
                     block_k=512, use_kernel=True):
    if not use_kernel or _on_cpu_lowering(k_cache.shape[2]):
        return ref.decode_attention_ref(q, k_cache, v_cache, lengths,
                                        window=window, scale=scale)
    return _decode.decode_attention(q, k_cache, v_cache, lengths,
                                    window=window, scale=scale,
                                    block_k=block_k, interpret=_on_cpu())


def verify_attention(q, k_cache, v_cache, lengths, *, scale=None,
                     use_kernel=True):
    """Speculative-verify attention over Q candidate positions in one call.

    q: (B, Hq, Q, D); position j attends over ``min(lengths + j, S)`` keys
    (``lengths`` = context + 1, the first position's key count).  The ref
    path batches all Q positions through ONE masked pass over the KV cache
    (the hot-path win: Q× fewer attention ops per layer); the kernel path
    unrolls Q calls of the flash decode kernel so accelerator numerics stay
    bit-identical to the plain one-token decode dispatch."""
    if not use_kernel or _on_cpu_lowering(k_cache.shape[2]):
        return ref.verify_attention_ref(q, k_cache, v_cache, lengths,
                                        scale=scale)
    S = k_cache.shape[2]
    outs = [_decode.decode_attention(q[:, :, j], k_cache, v_cache,
                                     jnp.minimum(lengths + j, S),
                                     scale=scale, interpret=_on_cpu())
            for j in range(q.shape[2])]
    return jnp.stack(outs, axis=2)


def paged_decode_attention(q, k_pages, v_pages, tables, lengths, *, window=0,
                           scale=None, use_kernel=True):
    """Block-table flash-decode: KV gathered from a shared page pool.

    q: (B, Hq, D); pages: (Hkv, P, T, D); tables: (B, N) int32."""
    if not use_kernel or _on_cpu_lowering(
            tables.shape[1] * k_pages.shape[2]):
        return ref.paged_decode_attention_ref(q, k_pages, v_pages, tables,
                                              lengths, window=window,
                                              scale=scale)
    return _paged_decode.paged_decode_attention(
        q, k_pages, v_pages, tables, lengths, window=window, scale=scale,
        interpret=_on_cpu())


def paged_prefill_attention(q, k_pages, v_pages, tables, k_suf, v_suf, *,
                            scale=None, use_kernel=True):
    """Chunked-prefill flash over block-table pages plus dense suffix rows.

    q: (B, Hq, C, D) suffix-chunk queries; pages: (Hkv, P, T, D) shared
    pool; tables: (B, N) int32 covering prior positions [0, N*T);
    k_suf/v_suf: (B, Hkv, Ssuf, D) dense suffix keys whose last C rows are
    the chunk's own (causally masked)."""
    total = tables.shape[1] * k_pages.shape[2] + k_suf.shape[2]
    if not use_kernel or _on_cpu_lowering(total):
        return ref.paged_prefill_attention_ref(q, k_pages, v_pages, tables,
                                               k_suf, v_suf, scale=scale)
    return _paged_prefill.paged_prefill_attention(
        q, k_pages, v_pages, tables, k_suf, v_suf, scale=scale,
        interpret=_on_cpu())


def quantize_wire(x, *, use_kernel=True):
    """Per-tensor symmetric int8 wire quantization of a float32 leaf.

    Returns (q: int8, scale: float32 scalar), byte-identical between the
    fused Pallas pass and the jnp ref (same max/round/clip chain)."""
    if not use_kernel or _on_cpu_lowering(x.size):
        return ref.quantize_int8_ref(x)
    return _quant.quantize_int8_fused(x, interpret=_on_cpu())


# single-step recurrent updates are trivially jnp (no kernel needed)
gla_step = ref.gla_step_ref
delta_step = ref.delta_step_ref
