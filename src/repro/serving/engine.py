"""Continuously-batched region engine: ONE scheduler loop for prefill
chunks and decode blocks.

``RegionScheduler`` is the region's state machine.  Every request moves

    queued -> prefilling -> [chunk-interleaved] -> ready -> decoding
           -> retired

  * **queued** — routed requests wait in a FIFO prefill queue owned by the
    scheduler (grouped on dequeue into same-bucket batches, so the
    recompile-free bucket property is preserved).
  * **prefilling** — one bucketed ``PrefillEngine.prefill`` call per unit;
    prompts past ``max_bucket`` become a **chunk-interleaved** unit instead:
    a ``ChunkedPrefill`` that advances ONE fixed-shape chunk per scheduler
    tick, so a long prompt never blocks decode for more than one chunk.
  * **ready** — prefill finished (KV trimmed / shipped); the request waits
    for the next decode block boundary.
  * **decoding** — ``admit_many`` places every ready request into free
    slots in one jit'd call at the block boundary, then ``step_block``
    advances all active streams ``block_size`` tokens in one dispatch.
    Slots freed by retiring streams are refilled at the NEXT boundary —
    decode never drains to empty while work is queued.
  * **retired** — budget exhausted or KV-capacity wall (the latter flagged
    ``Response.truncated`` and counted, never a fake clean finish).

One ``tick()`` = admit ready -> advance one prefill unit -> one decode
block.  The old alternating regime (prefill a whole batch, admit, drain to
empty, repeat) exists only as the measured baseline in
``benchmarks.engine_bench``.

``PrefillEngine`` (PrfaaS / PD-P): pow2 length x batch buckets compile
exactly once; per-request ``lengths`` keep padded results EXACT; past
``max_bucket`` prompts run as fixed-shape ``ChunkedPrefill`` chunks (the
``q_offset`` flash path + linear-mixer state carry), with compiles bounded
per chunk index.  ``warmup()`` precompiles the bucket grid AND the chunk
programs for past-``max_bucket`` lengths (chunk-count exact).

``DecodeEngine`` (PD-D): slot-based batched decode.  ``admit_many`` writes
K caches in one jit'd scatter; ``step_block`` runs ``block_size`` steps of
``model.decode_step`` in one jit'd ``lax.scan`` with the next token fed
back on-device.  An RNG key is threaded through the scan: with
``temperature > 0`` tokens are sampled (optionally top-k) from a
deterministic per-block key; the default ``temperature=0`` takes the
argmax through the identical program and stays bit-identical to the
pre-sampling engine.  The engine also integrates slot-occupancy telemetry
(``slot_busy_s`` / ``decode_wall_s`` / ``tokens_out``) so schedulers and
benchmarks can report decode-slot occupancy and goodput.  A dense block
also counts the KV tiles its decode-attention calls copy from HBM
(``kv_tiles_fetched``) against the tiles of the whole capacity
(``kv_tiles_capacity``), from the slot lengths on the host.

Compile counts are observable (``PrefillEngine.compiles``,
``DecodeEngine.block_compiles``) so benchmarks and tests can assert the
zero-recompile property instead of trusting it.

Every phase runs inside a ``serving.spans`` span (``prfaas.tick``,
``prfaas.admit``, ``prfaas.prefill.unit/chunk/finish``, and the decode
block as ``prfaas.decode.block``, split into ``prfaas.decode.dispatch``
-> ``.sync`` -> ``.bookkeep``); the walls above are read from those
spans.  Each ``Request`` is stamped on the same clock where its events
happen: ``t_submit`` (``submit``), ``t_unit_start`` (its prefill unit
formed), ``t_first`` (unit done), ``t_admit`` (``admit_many``) and
``t_finish`` (retired).

**Paged KV (``DecodeEngine(..., paged=True)``)** replaces the dense
per-slot buffers with the ``core.blockpool.BlockPool`` as the real device
cache layout (``models/paged.py``):

  * full-attn k/v live in shared page pools ``(R, Hkv, P, T, D)`` and MLA
    latents in ``(R, P, T, rank)``, where ``T`` is the pool's block size
    and ``P`` its page count + 1 sink page; linear/SSM state stays per-slot.
  * each slot addresses its pages through two host-side int32 block tables:
    ``seq`` ``(num_slots, capacity/T)`` for append-only full/MLA layers and
    ``ring`` ``(num_slots, W_buf/T)`` for SWA ring buffers.
  * ``admit_many`` writes only the request's *pages* in one jit'd scatter
    (no capacity-sized zero padding, no monolithic slot copy); a prefix hit
    maps the matched pages read-only into the slot's table head via
    BlockPool ref-counts instead of rewriting them.  ``step_block`` reads
    and appends through the tables (``kernels/paged_decode_attn.py``);
    retiring a slot releases its refs — prompt pages registered in the
    prefix cache stay LRU-resident, decode tail pages free immediately.

  Prefer ``paged_kv=False`` (the default, dense layout) when the arch has
  encoder/cross-attention blocks (unsupported), when slots are few and
  long-lived (dense buffers have no table indirection overhead), or when
  byte-identical legacy traces matter; paged pays off under prefix reuse
  and many short concurrent streams, where resident KV bytes track the
  *used* pages instead of ``num_slots x capacity``.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttentionSpec
from repro.core.blockpool import PREFIX, BlockPool
from repro.kernels.decode_attn import tiles_fetched
from repro.models import Model, prepare_decode_caches
from repro.models import paged as paged_mod
from repro.models.kvcache import cache_num_bytes, quantize_cache_for_wire
from repro.serving.api import Request, Response
from repro.serving.spans import span

_SEQ_LEAVES = ("k", "v", "ckv", "kpe")


def _dequant_pages(pg, dtype):
    """Admission page tensor -> pool dtype.  Wire-form pages ({"q": int8,
    "scale": (n_pages,) f32}) dequantize here, INSIDE the page-scatter
    program — fusing what used to be a separate full-cache
    ``dequantize_cache_from_wire`` pass before admission.  The op chain
    (int8 -> f32, multiply by the f32-upcast stored scale, cast to the pool
    dtype) is exactly the eager path's, so pool bytes are identical."""
    if isinstance(pg, dict):
        q, scale = pg["q"], pg["scale"]
        shape = [1] * q.ndim
        shape[2 if q.ndim == 5 else 1] = scale.shape[0]
        return (q.astype(jnp.float32) * scale.reshape(shape)).astype(dtype)
    return pg.astype(dtype)


def next_pow2(n: int, lo: int = 1) -> int:
    v = max(int(lo), 1)
    while v < n:
        v *= 2
    return v


def _jit_cache_size(fn) -> Optional[int]:
    size = getattr(fn, "_cache_size", None)
    return size() if callable(size) else None


class PrefillEngine:
    """Bucketed (and, past ``max_bucket``, chunked) prefill.

    ``min_bucket``: smallest length bucket (pow2).  ``max_bucket``: when
    set, prompts padded beyond it are prefetched in fixed ``max_bucket``-
    token chunks (decoder-only models).  ``pad_batch``: round the batch
    dimension up to a power of two as well (exactly one compile per
    (batch-bucket, length-bucket) pair).
    """

    def __init__(self, model: Model, params, *, min_bucket: int = 32,
                 max_bucket: Optional[int] = None, pad_batch: bool = True):
        self.model = model
        self.params = params
        self.min_bucket = next_pow2(min_bucket)
        if max_bucket is not None and next_pow2(max_bucket) != max_bucket:
            raise ValueError("max_bucket must be a power of two")
        self.max_bucket = max_bucket
        self.pad_batch = pad_batch
        self._prefill = jax.jit(self._prefill_impl)
        self._chunk = jax.jit(self.model.prefill_chunk)
        self._carry_last = jax.jit(self._carry_last_impl)
        self._finish = jax.jit(self._finish_impl)
        self._shape_keys = set()         # fallback compile tracking
        self.calls = 0
        self.tokens_prefilled = 0        # valid prompt tokens computed

    # ------------------------------------------------------------- jit fns
    def _prefill_impl(self, params, tokens, lengths):
        logits, caches = self.model.prefill(
            params, {"tokens": tokens, "lengths": lengths})
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

    @staticmethod
    def _carry_last_impl(hidden, last, lengths, offset):
        """Fold a chunk's hidden states (B, C, d) into the (B, 1, d)
        last-valid-hidden carry: rows whose final prompt position falls in
        [offset, offset+C) take their row from this chunk."""
        C = hidden.shape[1]
        pos = lengths.astype(jnp.int32) - 1
        idx = jnp.clip(pos - offset, 0, C - 1)
        cand = jnp.take_along_axis(hidden, idx[:, None, None], axis=1)
        in_chunk = (pos >= offset) & (pos < offset + C)
        return jnp.where(in_chunk[:, None, None], cand, last)

    def _finish_impl(self, params, hidden, lengths):
        logits = self.model.last_logits(params, hidden, lengths)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # ------------------------------------------------------------- buckets
    def bucket_for(self, max_len: int) -> int:
        return next_pow2(max_len, self.min_bucket)

    def is_chunked(self, length: int) -> bool:
        """True when a prompt of ``length`` tokens runs as chunked prefill
        (its bucket exceeds ``max_bucket``)."""
        return (self.max_bucket is not None
                and self.bucket_for(int(length)) > self.max_bucket)

    @property
    def compiles(self) -> int:
        """Number of distinct compiled prefill programs (actual jit-cache
        entries when the runtime exposes them, tracked shape keys else)."""
        sizes = [_jit_cache_size(f)
                 for f in (self._prefill, self._chunk, self._carry_last,
                           self._finish)]
        if any(s is None for s in sizes):
            return len(self._shape_keys)
        return sum(sizes)

    def warmup(self, batch_sizes: Sequence[int], lengths: Sequence[int],
               decode: Optional["DecodeEngine"] = None):
        """Compile every (batch-bucket, length-bucket) pair up front — and,
        for engines with ``max_bucket`` set, the chunked-prefill chunk
        programs past it.  Chunk warmup is chunk-count exact: a length L
        past the max bucket warms ``ceil(L / max_bucket)`` chunk programs
        (each chunk index is its own program — the prior-cache operand
        grows with the index), which covers every shorter chunked prompt;
        the pre-fix code rounded L up to a power of two first, compiling
        chunk programs no real prompt of length <= L ever reaches.

        Pass the region's ``decode`` engine to also warm its paged
        admission programs (the page-write scatter per pow2 page-count
        bucket) for the same traffic shape — a no-op for dense engines."""
        shapes = set()
        for l in lengths:
            if self.is_chunked(l):
                C = self.max_bucket
                shapes.add(-(-int(l) // C) * C)     # ceil to chunk multiple
            else:
                shapes.add(self.bucket_for(l))
        for b in sorted({next_pow2(b) for b in batch_sizes}):
            for l in sorted(shapes):
                toks = np.zeros((b, l), np.int32)
                self.prefill(toks, np.full((b,), l, np.int32))
        if decode is not None and getattr(decode, "paged", False):
            decode.warmup_admission(batch_sizes, lengths)
        if decode is not None:
            decode.warmup_block()

    def _pad(self, tokens: np.ndarray, lengths):
        """Pad a (B, S) prompt batch to its schedulable shape: pow2 length
        bucket (or chunk-multiple past ``max_bucket``) x pow2 batch bucket.
        Returns (toks, lens, B, chunked)."""
        tokens = np.asarray(tokens)
        B, S = tokens.shape
        if lengths is None:
            lengths = np.full((B,), S, np.int32)
        lengths = np.asarray(lengths, np.int32)
        max_len = int(lengths.max()) if B else S
        Sb = self.bucket_for(max_len)
        chunked = self.max_bucket is not None and Sb > self.max_bucket
        if chunked:
            C = self.max_bucket
            Sb = -(-max_len // C) * C                    # ceil to chunks
        Bb = next_pow2(B) if self.pad_batch else B
        toks = np.zeros((Bb, Sb), np.int32)
        toks[:B, :min(S, Sb)] = tokens[:, :Sb]
        lens = np.ones((Bb,), np.int32)                  # pad rows: 1 token
        lens[:B] = np.maximum(lengths, 1)
        return toks, lens, B, chunked

    # -------------------------------------------------------------- public
    def prefill(self, tokens: np.ndarray, lengths=None):
        """tokens: (B, S) right-padded prompts; lengths: (B,) valid counts
        (defaults to S).  Returns (first_token (B,), caches, wall_s).

        The returned caches are bucket-padded; slice a request out with
        ``trim_request_cache(caches, i, length)`` before shipping so wire
        bytes reflect the prompt, not the bucket.
        """
        with span("prfaas.prefill.unit") as sp:
            toks, lens, B, chunked = self._pad(tokens, lengths)
            self.calls += 1
            if chunked:
                cp = ChunkedPrefill(self, toks, lens, B)
                while not cp.done:
                    cp.step()
                first, caches = cp.finish()
            else:
                Bb, Sb = toks.shape
                self._shape_keys.add(("prefill", Bb, Sb))
                first, caches = self._prefill(self.params, jnp.asarray(toks),
                                              jnp.asarray(lens))
                self.tokens_prefilled += int(lens[:B].sum())
            jax.block_until_ready(first)
            first = np.asarray(first)[:B]
        return first, caches, sp.seconds

    def start_chunked(self, tokens: np.ndarray, lengths=None
                      ) -> "ChunkedPrefill":
        """Begin an incremental chunked prefill the scheduler can advance
        one chunk at a time (``ChunkedPrefill.step`` between decode
        blocks).  The prompt batch must be past ``max_bucket``."""
        toks, lens, B, chunked = self._pad(tokens, lengths)
        if not chunked:
            raise ValueError("prompt fits a plain bucket; use prefill()")
        self.calls += 1
        return ChunkedPrefill(self, toks, lens, B)

    def start_suffix(self, tokens, prior_caches, cached_len: int
                     ) -> "ChunkedPrefill":
        """Suffix-only prefill for a device prefix hit: compute tokens
        [cached_len, L) as fixed-shape chunks over the prior caches
        (positions offset by ``cached_len``; the chunked-prefill
        ``q_offset`` path masks exactly as a full prefill would, so the
        resulting tokens and merged caches are identical — only the
        cached-prefix FLOPs are skipped).  Batch of 1, scheduled like a
        chunked unit."""
        full = np.asarray(tokens, np.int32).reshape(-1)
        suffix = full[cached_len:]
        n_suffix = int(suffix.shape[0])
        if n_suffix <= 0:
            raise ValueError("suffix prefill needs >= 1 uncached token")
        C = self.bucket_for(n_suffix)
        if self.max_bucket is not None:
            C = min(C, self.max_bucket)
        n_chunks = -(-n_suffix // C)
        toks = np.zeros((1, n_chunks * C), np.int32)
        toks[0, :n_suffix] = suffix
        self.calls += 1
        return ChunkedPrefill(self, toks, np.array([n_suffix], np.int32), 1,
                              caches=prior_caches, pos_offset=cached_len,
                              chunk=C)


class ChunkedPrefill:
    """One in-flight chunked prefill, schedulable a fixed-shape chunk at a
    time — the unit ``RegionScheduler`` interleaves between decode blocks.

    ``step()`` runs ONE ``max_bucket``-token chunk through
    ``model.prefill_chunk`` (attention chunks attend over the prior cache
    via ``q_offset``; linear mixers carry state) and folds the chunk's
    hidden states into the (B, 1, d) last-valid-hidden carry;
    ``finish()`` computes the first decode token from the carry.  Wall time
    is accumulated across steps so callers account the full prefill cost.
    """

    def __init__(self, eng: PrefillEngine, toks: np.ndarray,
                 lens: np.ndarray, n_valid: int, *, caches=None,
                 pos_offset: int = 0, chunk: Optional[int] = None):
        self.eng = eng
        self.toks = toks                     # (Bb, Sb), Sb = n_chunks * C
        self.lens = lens
        self.n_valid = n_valid               # real (unpadded) rows
        self.C = eng.max_bucket if chunk is None else chunk
        self.n_chunks = toks.shape[1] // self.C
        self.i = 0                           # next chunk index
        # suffix-prefill mode: ``caches`` already cover [0, pos_offset) and
        # the chunk positions (RoPE phases, causal masks) start there;
        # ``lens`` then count SUFFIX tokens, not the full prompt
        self.caches = caches
        self.off = int(pos_offset)
        # table-direct suffix prefill: the prior caches carry pool page
        # leaves + block tables ("pk"/"pv"/"tbl", see paged.build_prior)
        # instead of a gathered dense prior — a distinct chunk program
        self.table_direct = caches is not None and any(
            getattr(p[-1], "key", None) == "pk"
            for p, _ in jax.tree_util.tree_flatten_with_path(caches)[0])
        self._last = None                    # (Bb, 1, d) last-hidden carry
        self._lens_dev = jnp.asarray(lens)
        self.wall_s = 0.0

    @property
    def done(self) -> bool:
        return self.i >= self.n_chunks

    def step(self) -> bool:
        """Advance one chunk; returns True once all chunks have run."""
        eng, C, i = self.eng, self.C, self.i
        with span("prfaas.prefill.chunk") as sp:
            Bb = self.toks.shape[0]
            eng._shape_keys.add(("chunk", Bb, C, i, self.off)
                                + (("paged",) if self.table_direct else ()))
            pos = np.broadcast_to(
                np.arange(self.off + i * C, self.off + (i + 1) * C,
                          dtype=np.int32)[None], (Bb, C))
            chunk_lens = np.clip(self.lens - i * C, 0, C).astype(np.int32)
            h, self.caches = eng._chunk(
                eng.params,
                {"tokens": jnp.asarray(self.toks[:, i * C:(i + 1) * C]),
                 "positions": jnp.asarray(pos),
                 "lengths": jnp.asarray(chunk_lens)},
                self.caches)
            if self._last is None:
                self._last = jnp.zeros((Bb, 1, h.shape[-1]), h.dtype)
            self._last = eng._carry_last(h, self._last, self._lens_dev,
                                         jnp.int32(i * C))
            eng._shape_keys.add(("carry", Bb, C))
            self.i += 1
            if self.done:
                jax.block_until_ready(self._last)
        self.wall_s += sp.seconds
        return self.done

    def finish(self):
        """Epilogue after the last ``step()``: returns (first_token
        (n_valid,) np.int32, caches)."""
        if not self.done:
            raise RuntimeError(f"chunked prefill at chunk {self.i}"
                               f"/{self.n_chunks}; not finished")
        with span("prfaas.prefill.finish") as sp:
            Bb = self.toks.shape[0]
            self.eng._shape_keys.add(("finish", Bb))
            first = self.eng._finish(self.eng.params, self._last,
                                     jnp.ones((Bb,), jnp.int32))
            jax.block_until_ready(first)
            self.eng.tokens_prefilled += int(self.lens[:self.n_valid].sum())
        self.wall_s += sp.seconds
        caches = self.caches
        if self.table_direct:
            # the pool pages/tables were only operands for the chunk steps;
            # the returned payload keeps the dense suffix rows (plus the
            # "off" marker recording where they start) for trim + admission
            caches = _strip_prior_pages(caches)
        return np.asarray(first)[:self.n_valid], caches


def _attention_calls(cfg, capacity: int):
    """``[((S, kv_heads, dk, dv, itemsize), layers)]``: the dense
    decode-attention calls of one decode step, by cache geometry (MLA
    attends over its latent as one KV head; cross-attention is left out)."""
    itemsize = 2 if cfg.dtype == "bfloat16" else 4
    calls: Dict[tuple, int] = {}
    for *_, b in cfg.iter_blocks():
        m = b.mixer
        if not isinstance(m, AttentionSpec):
            continue
        if m.kind == "mla":
            geo = (capacity, 1, m.mla_kv_rank + m.mla_rope_dim,
                   m.mla_kv_rank, itemsize)
        else:
            geo = (m.kv_cache_tokens(capacity), m.kv_heads, m.head_dim,
                   m.head_dim, itemsize)
        calls[geo] = calls.get(geo, 0) + 1
    return list(calls.items())


class DecodeEngine:
    """Slot-based continuous batching decode cluster (see module doc)."""

    def __init__(self, model: Model, params, num_slots: int, capacity: int,
                 block_size: int = 8, *, temperature: float = 0.0,
                 top_k: int = 0, seed: int = 0, paged: bool = False,
                 pool: Optional[BlockPool] = None, page_tokens: int = 16,
                 spec_k: int = 0, spec_ngram: int = 2):
        self.model = model
        self.params = params
        self.num_slots = num_slots
        self.capacity = capacity
        self.block_size = max(1, int(block_size))
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self._key = jax.random.PRNGKey(int(seed))
        self._blocks = 0               # step_block dispatch counter
        self._steps = 0                # tokens-emitted counter (RNG fold_in)
        self.paged = bool(paged)
        self.spec_k = max(0, int(spec_k))
        self.spec_ngram = max(1, int(spec_ngram))
        if self.spec_k and self.temperature > 0.0:
            raise ValueError("speculative decode verifies the longest "
                             "greedy-matching prefix; it requires "
                             "temperature=0 (got spec_k "
                             f"{self.spec_k}, temperature {temperature})")
        if self.spec_k:
            # SWA ring rollback restores the q = spec_k + 1 rows a verify
            # dispatch overwrites; the per-slot row indices are distinct
            # only while q <= w_buf
            w_min = min([min(b.mixer.window, capacity)
                         for g in model.cfg.groups for b in g.blocks
                         if getattr(b.mixer, "kind", "") == "swa"
                         and getattr(b.mixer, "window", 0) > 0]
                        or [capacity])
            if self.spec_k + 1 > w_min:
                raise ValueError(f"spec_k + 1 = {self.spec_k + 1} exceeds "
                                 f"the smallest SWA ring buffer ({w_min})")
        if self.paged:
            if pool is None:
                # standalone default: same token headroom the dense layout
                # reserves (num_slots * capacity), as pool pages
                pool = BlockPool(num_slots * capacity // page_tokens,
                                 page_tokens)
            if pool.block_tokens != page_tokens:
                raise ValueError(
                    f"pool block_tokens {pool.block_tokens} != "
                    f"page_tokens {page_tokens}")
            self.pool = pool
            lay = paged_mod.paged_layout(model.cfg, capacity, page_tokens,
                                         pool.num_blocks)
            self._layout = lay
            self.caches = jax.jit(lambda: paged_mod.init_paged_cache(
                model.cfg, num_slots, lay))()
            # device bytes one pool page occupies across every paged leaf
            # (one page id addresses the same row in ALL attention layers)
            self.page_bytes = paged_mod.page_bytes(model.cfg, lay)
            # host-side block tables; retired/empty rows point at the sink
            self.table_seq = np.full((num_slots, lay.seq_cols), lay.sink,
                                     np.int32)
            self.table_ring = np.full((num_slots, lay.ring_cols), lay.sink,
                                      np.int32)
            self._slot_shared: List[List[int]] = [[] for _ in range(num_slots)]
            self._slot_owned: List[List[int]] = [[] for _ in range(num_slots)]
            self._seq_pages: List[List[int]] = [[] for _ in range(num_slots)]
            self._block_paged = jax.jit(self._block_paged_impl,
                                        donate_argnums=(2,))
            self._write_pages = jax.jit(self._write_pages_impl,
                                        donate_argnums=(0,))
            # deployment hooks: prefix-cache registration at admission (page
            # content is final then) and pin accounting at retirement
            self.on_admit = None       # fn(req, prompt_len, seq_ids, snap)
            self.on_retire = None      # fn(rid)
            self.page_fail_retires = 0
            self._warming = False      # hooks muted during warmup_admission
            # deployments shipping int8 wire pytrees set this so
            # warmup_admission also warms the dequantize-in-scatter
            # program variant (wire payloads have a distinct operand tree)
            self.wire_admission = False
        else:
            self.pool = pool
            self.caches = jax.jit(
                lambda: model.init_cache(num_slots, capacity))()
            self._warming = False
        # speculative decode: per-slot token history (prompt + emitted) for
        # the device-resident n-gram drafter, plus accept telemetry
        self._hist = np.zeros((num_slots, capacity), np.int32)
        self.verify_rounds = 0
        self.accepted_tokens = 0
        if self.spec_k:
            self._block_spec = jax.jit(self._block_spec_impl,
                                       donate_argnums=(2,))
            if self.paged:
                self._block_spec_paged = jax.jit(self._block_spec_paged_impl,
                                                 donate_argnums=(2,))
        # per-request time-between-tokens: wall seconds from the first token
        # (``Request.t_first``) to retirement per emitted token after it
        self.tbt_s: List[float] = []
        self.lengths = np.zeros((num_slots,), np.int32)
        self.tokens = np.zeros((num_slots,), np.int32)
        self.active = np.zeros((num_slots,), bool)
        self.budget = np.zeros((num_slots,), np.int32)
        self.slot_req: List[Optional[int]] = [None] * num_slots
        self._slot_request: List[Optional[Request]] = [None] * num_slots
        self.outputs: Dict[int, Response] = {}
        self.truncations = 0
        # occupancy telemetry: wall seconds spent inside step_block, the
        # same seconds weighted by #active slots, and tokens emitted —
        # occupancy = slot_busy_s / (num_slots * makespan), goodput =
        # tokens_out / makespan for whatever makespan the caller measures
        self.decode_wall_s = 0.0
        self.slot_busy_s = 0.0
        self.tokens_out = 0
        # dense blocks: KV tiles the decode-attention kernel copies, and
        # the tiles of the whole capacity, summed over attention layers
        self.kv_tiles_fetched = 0
        self.kv_tiles_capacity = 0
        self._attn_calls = _attention_calls(model.cfg, capacity)
        self._block_steps = np.arange(1, self.block_size + 1,
                                      dtype=np.int32)[:, None]
        self._free = deque(range(num_slots))
        self._step = jax.jit(model.decode_step, donate_argnums=(2,))
        self._block = jax.jit(self._block_impl, donate_argnums=(2,))
        self._place_many = jax.jit(self._place_many_impl, donate_argnums=(0,))

    # ---------------------------------------------------------------- admit
    @staticmethod
    def _place_many_impl(caches, payloads, slots):
        """Write K request caches into their slots in ONE jit'd call.

        ``payloads``: tuple of K prepared caches (slot axis = 1, size 1);
        ``slots``: (K,) int32.  Lowered as K in-place slot updates on the
        donated buffers — one dispatch total, vs the old one-jit-call-per-
        request admission."""
        def place(buf, *news):
            for j, new in enumerate(news):
                buf = jax.lax.dynamic_update_slice_in_dim(
                    buf, new.astype(buf.dtype), slots[j], axis=1)
            return buf

        return jax.tree.map(place, caches, *payloads)

    # ---------------------------------------------------------- paged admit
    def _write_pages_impl(self, caches, seq_pages, ids_seq, ring_pages,
                          ids_ring, states, slots):
        """One scatter for a whole paged admission: every full/MLA layer's
        new pages land at ``ids_seq`` in its pool, every SWA layer's ring
        pages at ``ids_ring``; linear state is K per-slot updates.  Padded
        id tails repeat the last id with the same payload page — duplicate
        writes of identical content, harmless."""
        cfg = self.model.cfg
        groups = []
        for gi, g in enumerate(cfg.groups):
            gc = {}
            for bi, b in enumerate(g.blocks):
                key = f"b{bi}"
                m = b.mixer
                leaves = caches["groups"][gi][key]
                if paged_mod._is_ring(m):
                    pg = ring_pages[gi][key]
                    gc[key] = {n: leaves[n].at[:, :, ids_ring].set(
                        pg[n].astype(leaves[n].dtype)) for n in leaves}
                elif paged_mod._is_seq(m):
                    pg = seq_pages[gi][key]
                    if m.kind == "mla":
                        gc[key] = {n: leaves[n].at[:, ids_seq].set(
                            _dequant_pages(pg[n], leaves[n].dtype))
                            for n in leaves}
                    else:
                        gc[key] = {n: leaves[n].at[:, :, ids_seq].set(
                            _dequant_pages(pg[n], leaves[n].dtype))
                            for n in leaves}
                else:
                    def place(buf, *news):
                        for j, new in enumerate(news):
                            buf = jax.lax.dynamic_update_slice_in_dim(
                                buf, new.astype(buf.dtype), slots[j], axis=1)
                        return buf
                    gc[key] = jax.tree.map(
                        place, leaves, *[s[gi][key] for s in states])
            groups.append(gc)
        return {"groups": groups}

    @staticmethod
    def _cat_pad(parts, n_pad: int, axis: int):
        """Concatenate page tensors along their page axis and pad to
        ``n_pad`` pages by repeating the last page."""
        x = jnp.concatenate(parts, axis=axis) if len(parts) > 1 else parts[0]
        n = x.shape[axis]
        if n < n_pad:
            idx = [slice(None)] * x.ndim
            idx[axis] = slice(n - 1, n)
            reps = [1] * x.ndim
            reps[axis] = n_pad - n
            x = jnp.concatenate([x, jnp.tile(x[tuple(idx)], reps)], axis=axis)
        return x

    def _gather_pages(self, payloads, kind: str, n_pad: int):
        """Merge per-entry admission payloads of one kind ("seq"/"ring")
        into the single padded operand tree ``_write_pages`` consumes.

        Wire-form parts (int8 ``{"q", "scale"}`` page tensors) stay
        quantized: the per-request scalar scales broadcast into one
        per-page scale vector and the scatter dequantizes in place.  A
        batch mixing wire and raw payloads (e.g. an offloaded flow admitted
        alongside a local prefix-hit suffix) dequantizes its wire parts
        here instead, keeping one scatter program shape."""
        out = []
        for gi in range(len(self.model.cfg.groups)):
            if payloads[0][kind][gi] is None:
                out.append(None)
                continue
            gd = {}
            for key, d0 in payloads[0][kind][gi].items():
                gd[key] = {}
                for name in d0:
                    parts = [p[kind][gi][key][name] for p in payloads]
                    wire = [isinstance(x, dict) for x in parts]
                    if all(wire):
                        qs = [x["q"] for x in parts]
                        axis = 2 if qs[0].ndim == 5 else 1   # k/v vs MLA
                        scales = jnp.concatenate([
                            jnp.broadcast_to(
                                jnp.asarray(x["scale"],
                                            jnp.float32).reshape((1,)),
                                (x["q"].shape[axis],)) for x in parts])
                        ns = scales.shape[0]
                        if ns < n_pad:
                            scales = jnp.concatenate(
                                [scales, jnp.broadcast_to(scales[-1:],
                                                          (n_pad - ns,))])
                        gd[key][name] = {
                            "q": self._cat_pad(qs, n_pad, axis),
                            "scale": scales}
                        continue
                    if any(wire):
                        parts = [
                            (x["q"].astype(jnp.float32)
                             * jnp.asarray(x["scale"], jnp.float32)
                             ).astype(x["scale"].dtype)
                            if isinstance(x, dict) else x for x in parts]
                    axis = 2 if parts[0].ndim == 5 else 1    # k/v vs MLA
                    gd[key][name] = self._cat_pad(parts, n_pad, axis)
            out.append(gd)
        return out

    def _admit_paged(self, entries: Sequence[Tuple]) -> int:
        lay = self._layout
        T = lay.page_tokens
        taken = []
        for (req, first, cache, L) in entries[:len(self._free)]:
            pin = getattr(req, "device_pin", None)
            c = pin.cached_len if pin is not None else 0
            need_seq = -(-(L - c) // T) if lay.seq_cols else 0
            ids = self.pool.allocate(need_seq + lay.ring_cols, PREFIX)
            if ids is None:
                break              # pool exhausted: request stays ready
            taken.append((req, first, cache, L, pin, c,
                          list(ids[:need_seq]), list(ids[need_seq:])))
        if not taken:
            return 0
        n = len(taken)
        slots = [self._free.popleft() for _ in range(n)]
        payloads = [paged_mod.build_admit_payload(self.model.cfg, cache, lay,
                                                  c, L)
                    for (_, _, cache, L, _, c, _, _) in taken]
        # one padded scatter: pow2 page counts + pow2 state-entry count
        ids_seq = [b for t in taken for b in t[6]]
        ids_ring = [b for t in taken for b in t[7]]
        if ids_seq:
            np_seq = next_pow2(len(ids_seq))
            seq_tree = self._gather_pages(payloads, "seq", np_seq)
            ids_seq += [ids_seq[-1]] * (np_seq - len(ids_seq))
        else:
            seq_tree, ids_seq = None, [0]
        if ids_ring:
            np_ring = next_pow2(len(ids_ring))
            ring_tree = self._gather_pages(payloads, "ring", np_ring)
            ids_ring += [ids_ring[-1]] * (np_ring - len(ids_ring))
        else:
            ring_tree, ids_ring = None, [0]
        K = next_pow2(n)
        states = [p["state"] for p in payloads]
        states += [states[-1]] * (K - n)
        pad_slots = slots + [slots[-1]] * (K - n)
        self.caches = self._write_pages(
            self.caches, seq_tree, jnp.asarray(ids_seq, jnp.int32),
            ring_tree, jnp.asarray(ids_ring, jnp.int32), tuple(states),
            jnp.asarray(pad_slots, jnp.int32))
        for slot, payload, (req, first, _, L, pin, c, seq_new, ring_ids) in \
                zip(slots, payloads, taken):
            shared = list(pin.seq_ids) if pin is not None else []
            seq_all = shared + seq_new
            self.table_seq[slot, :] = lay.sink
            self.table_seq[slot, :len(seq_all)] = seq_all
            self.table_ring[slot, :] = lay.sink
            self.table_ring[slot, :len(ring_ids)] = ring_ids
            self._slot_shared[slot] = shared
            self._slot_owned[slot] = seq_new + ring_ids
            self._seq_pages[slot] = seq_all
            self.lengths[slot] = L
            self.tokens[slot] = first
            self.active[slot] = True
            self.budget[slot] = req.max_new_tokens
            self.slot_req[slot] = req.rid
            self.outputs[req.rid] = Response(req.rid, [int(first)])
            self._seed_slot_history(slot, req, first, L)
            if self.on_admit is not None and not self._warming:
                snap = ({"ring": payload["ring"], "state": payload["state"]}
                        if L % T == 0 else None)
                self.on_admit(req, L, seq_all, snap)
        self._stamp_admitted(t[0] for t in taken)
        return n

    def _ensure_pages(self):
        """Before a decode block: grow each active slot's seq table to cover
        the block's writes.  A slot the pool cannot serve retires truncated
        (the paged analogue of the dense capacity wall)."""
        lay = self._layout
        if not lay.seq_cols:
            return
        T = lay.page_tokens
        # speculative blocks advance up to spec_k + 1 tokens per round
        stride = self.block_size * (self.spec_k + 1)
        for slot in np.where(self.active)[0]:
            end = min(int(self.lengths[slot]) + stride, self.capacity)
            need = -(-end // T)
            have = len(self._seq_pages[slot])
            if need <= have:
                continue
            ids = self.pool.allocate(need - have, PREFIX)
            if ids is None:
                self.page_fail_retires += 1
                self._retire(int(slot), force_truncate=True)
                continue
            self.table_seq[slot, have:need] = ids
            self._seq_pages[slot].extend(ids)
            self._slot_owned[slot].extend(ids)

    def _block_paged_impl(self, params, tokens, caches, lengths, key, step0,
                          tables):
        """Paged twin of ``_block_impl``: the block tables ride into every
        ``decode_step`` (page geometry is closure-static)."""
        lay = self._layout

        def body(carry, i):
            toks, caches, lens = carry
            sub = jax.random.fold_in(key, step0 + i)
            logits, caches = self.model.decode_step(
                params, toks, caches, lens, tables=tables,
                page_tokens=lay.page_tokens, capacity=self.capacity)
            nxt = self._select(logits, sub)
            return (nxt, caches, lens + 1), nxt

        (_, caches, _), toks = jax.lax.scan(
            body, (tokens, caches, lengths),
            jnp.arange(self.block_size, dtype=jnp.int32))
        return toks, caches

    def warmup_admission(self, batch_sizes: Sequence[int],
                         lengths: Sequence[int]):
        """Precompile the paged-admission scatter programs (pow2 page-count
        x state-entry buckets) for the given traffic shape: zero-payload
        requests are admitted into real slots and immediately retired, so
        the pool round-trips (allocated == freed) and live traffic finds
        every program warm."""
        if not self.paged:
            return
        self._warming = True
        try:
            for b in sorted({next_pow2(min(int(x), self.num_slots))
                             for x in batch_sizes}):
                for l in sorted({int(x) for x in lengths}):
                    payload = paged_mod.zero_request_payload(self.model.cfg,
                                                             l)
                    payloads = [payload]
                    if self.wire_admission:
                        from repro.models.kvcache import \
                            quantize_cache_for_wire
                        payloads.append(quantize_cache_for_wire(payload)[0])
                    for p in payloads:
                        entries = [(Request(rid=-(10_000 + i),
                                            tokens=np.zeros((l,), np.int32),
                                            max_new_tokens=1), 0, p, l)
                                   for i in range(b)]
                        self.admit_many(entries)
                        for slot in range(self.num_slots):
                            rid = self.slot_req[slot]
                            if rid is not None and rid <= -10_000:
                                self._retire(slot)
                                self.outputs.pop(rid, None)
        finally:
            self._warming = False

    @property
    def admit_compiles(self) -> Optional[int]:
        """Distinct compiled paged-admission scatter programs."""
        return _jit_cache_size(self._write_pages) if self.paged else 0

    def free_slots(self) -> List[int]:
        return list(self._free)

    def admit(self, req: Request, first_token: int, one_cache,
              prompt_len: int) -> bool:
        """Place one request's shipped KV into a free slot."""
        return self.admit_many([(req, first_token, one_cache,
                                 prompt_len)]) == 1

    def admit_many(self, entries: Sequence[Tuple]) -> int:
        """entries: [(req, first_token, one_cache, prompt_len), ...].
        Admits up to the number of free slots (in order); returns the
        number admitted.  One jit'd scatter regardless of K; K is padded to
        a power of two (repeating the last entry) to bound compiles.

        Paged mode writes only each request's *pages* (and honors
        ``req.device_pin``: the pinned prefix pages are mapped, not
        rewritten); admission then also needs pool pages, so it may admit
        fewer than the free-slot count."""
        if self.paged:
            return self._admit_paged(entries)
        n = min(len(entries), len(self._free))
        if n == 0:
            return 0
        take = list(entries[:n])
        slots = [self._free.popleft() for _ in range(n)]
        placed = [prepare_decode_caches(self.model.cfg, c, self.capacity)
                  for (_, _, c, _) in take]
        K = next_pow2(n)
        pad_slots = slots + [slots[-1]] * (K - n)   # duplicate writes of the
        placed += [placed[-1]] * (K - n)            # same payload: harmless
        self.caches = self._place_many(self.caches, tuple(placed),
                                       jnp.asarray(pad_slots, jnp.int32))
        for slot, (req, first_token, _, prompt_len) in zip(slots, take):
            self.lengths[slot] = prompt_len
            self.tokens[slot] = first_token
            self.active[slot] = True
            self.budget[slot] = req.max_new_tokens
            self.slot_req[slot] = req.rid
            self.outputs[req.rid] = Response(req.rid, [int(first_token)])
            self._seed_slot_history(slot, req, first_token, prompt_len)
        self._stamp_admitted(req for req, *_ in take)
        return n

    @staticmethod
    def _stamp_admitted(reqs):
        t = time.perf_counter()
        for req in reqs:
            req.t_admit = t

    def _seed_slot_history(self, slot: int, req: Request, first_token: int,
                           prompt_len: int):
        """The slot's request, and the drafter history (prompt + first
        token)."""
        self._slot_request[slot] = req
        if self.spec_k:
            self._hist[slot, :] = 0
            L = min(prompt_len, self._hist.shape[1])
            self._hist[slot, :L] = np.asarray(req.tokens[:L], np.int32)
            if prompt_len < self._hist.shape[1]:
                self._hist[slot, prompt_len] = first_token

    # ----------------------------------------------------------------- step
    def _retire(self, slot: int, force_truncate: bool = False):
        rid = self.slot_req[slot]
        resp = self.outputs[rid]
        resp.finished = True
        # at the KV-capacity wall with budget remaining: NOT a clean finish
        # (force_truncate: the paged pool ran out of pages mid-stream)
        truncated = force_truncate or (self.lengths[slot] >= self.capacity - 1
                                       and self.budget[slot] > 0)
        resp.truncated = bool(truncated)
        self.truncations += int(truncated)
        req = self._slot_request[slot]
        req.t_finish = time.perf_counter()
        if req.t_first is not None and not self._warming:
            self.tbt_s.append((req.t_finish - req.t_first)
                              / max(1, len(resp.output_tokens) - 1))
        self.active[slot] = False
        self.slot_req[slot] = None
        self._slot_request[slot] = None
        self._free.append(slot)
        if self.paged:
            # drop the prefix pins and this slot's own pages: registered
            # (populated) prompt pages stay LRU-resident for later hits,
            # decode-tail/ring pages free immediately.  The table rows point
            # at the sink so in-flight garbage writes land where no live
            # request reads.
            self.pool.release(self._slot_shared[slot])
            self.pool.release(self._slot_owned[slot])
            self._slot_shared[slot] = []
            self._slot_owned[slot] = []
            self._seq_pages[slot] = []
            self.table_seq[slot, :] = self._layout.sink
            self.table_ring[slot, :] = self._layout.sink
            if self.on_retire is not None and not self._warming:
                self.on_retire(rid)

    def step(self):
        """One decode iteration for all active slots (one host round-trip
        per token — the measured baseline for ``step_block``). Returns
        #active."""
        if self.paged:
            raise RuntimeError("the paged engine decodes in blocks "
                               "(page growth is per-block); use step_block")
        if not self.active.any():
            return 0
        logits, self.caches = self._step(
            self.params, jnp.asarray(self.tokens),
            self.caches, jnp.asarray(self.lengths))
        nxt = np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        for i in range(self.num_slots):
            if not self.active[i]:
                continue
            rid = self.slot_req[i]
            self.outputs[rid].output_tokens.append(int(nxt[i]))
            self.lengths[i] += 1
            self.tokens[i] = nxt[i]
            self.budget[i] -= 1
            if self.budget[i] <= 0 or self.lengths[i] >= self.capacity - 1:
                self._retire(i)
        return int(self.active.sum())

    def _select(self, logits, key):
        """Next-token rule traced into the block program.  ``temperature``
        and ``top_k`` are Python-static, so the default greedy engine traces
        the exact pre-sampling argmax graph (bit-identical tokens); with
        ``temperature > 0`` tokens are sampled, optionally from the top-k
        renormalized logits (``top_k=1`` degenerates to greedy)."""
        if self.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits / jnp.float32(self.temperature)
        if self.top_k > 0:
            kth = jax.lax.top_k(logits, self.top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

    def _block_impl(self, params, tokens, caches, lengths, key, step0):
        """``block_size`` decode steps fully on-device.  The sampling key
        for scan step ``i`` is ``fold_in(key, step0 + i)`` — indexed by
        tokens emitted, not by dispatch, so a sampled stream is reproducible
        no matter how the scheduler partitions it into blocks (and so the
        variable-stride speculative accounting can share the counter)."""
        def body(carry, i):
            toks, caches, lens = carry
            sub = jax.random.fold_in(key, step0 + i)
            logits, caches = self.model.decode_step(params, toks, caches,
                                                    lens)
            nxt = self._select(logits, sub)
            return (nxt, caches, lens + 1), nxt

        (_, caches, _), toks = jax.lax.scan(
            body, (tokens, caches, lengths),
            jnp.arange(self.block_size, dtype=jnp.int32))
        return toks, caches

    # --------------------------------------------------- speculative decode
    def _draft(self, hist, lens):
        """n-gram / prompt-lookup drafter, fully on-device: propose
        ``spec_k`` tokens per slot by suffix-matching the last ``spec_ngram``
        tokens of ``hist[b, :lens[b]+1]`` (prompt + everything emitted)
        against every earlier position and replaying what followed the most
        recent match.  No second model — drafts are just gathered history.
        Slots without a match (or reading past their frontier) propose
        whatever lies there; a wrong draft only costs its rejection."""
        n, k = self.spec_ngram, self.spec_k
        B, C = hist.shape
        pos = jnp.arange(C, dtype=jnp.int32)[None, :]
        ok = (pos >= n - 1) & (pos < lens[:, None])
        for d in range(n):
            shifted = hist if d == 0 else \
                jnp.pad(hist, ((0, 0), (d, 0)))[:, :C]
            tgt = jnp.take_along_axis(
                hist, jnp.clip(lens[:, None] - d, 0, C - 1), axis=1)
            ok &= (shifted == tgt)
        j = jnp.max(jnp.where(ok, pos, -1), axis=1)      # latest match or -1
        cols = jnp.clip(j[:, None] + 1 + jnp.arange(k, dtype=jnp.int32),
                        0, C - 1)
        return jnp.take_along_axis(hist, cols, axis=1)   # (B, k)

    def _spec_round(self, params, toks, caches, lens, hist, tables=None):
        """One draft -> verify -> accept -> commit round for every slot.
        Greedy acceptance: step j's prediction is compared against draft j;
        ``accept[b]`` = length of the matching prefix, and the (always
        correct) prediction after the last accepted draft rides along as a
        bonus token — so every round emits accept+1 tokens, ≥ 1."""
        k = self.spec_k
        q = k + 1
        kw = {}
        if tables is not None:
            kw = dict(tables=tables, page_tokens=self._layout.page_tokens,
                      capacity=self.capacity)
        drafts = self._draft(hist, lens)
        seq = jnp.concatenate([toks[:, None], drafts], axis=1)
        logits, caches, pending = self.model.decode_verify(
            params, seq, caches, lens, **kw)
        preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)    # (B, q)
        match = (preds[:, :k] == drafts).astype(jnp.int32)
        accept = jnp.sum(jnp.cumprod(match, axis=1), axis=1)     # (B,)
        caches = self.model.commit_verify(caches, pending, lens, accept, q,
                                          **kw)
        nxt = jnp.take_along_axis(preds, accept[:, None], axis=1)[:, 0]
        # history frontier: positions lens+1+j take preds[j] for accepted j
        # (rejected columns route out of range and drop), keeping the
        # invariant hist[b, :lens[b]+1] == prompt + accepted stream
        B, C = hist.shape
        step = jnp.arange(q, dtype=jnp.int32)[None, :]
        cols = jnp.where(step <= accept[:, None],
                         lens[:, None] + 1 + step, C)
        hist = hist.at[jnp.arange(B)[:, None], cols].set(preds, mode="drop")
        return caches, lens + accept + 1, hist, nxt, preds, accept

    def _block_spec_impl(self, params, tokens, caches, lengths, hist):
        """Speculative twin of ``_block_impl``: ``block_size`` verify rounds
        on-device, each emitting a VARIABLE 1..spec_k+1 tokens per slot.
        The accept-counts thread the scan carry (lengths advance by
        accept+1), and the stacked (round, slot, q) predictions + accepts go
        back to the host for variable-stride budget/retire accounting."""
        def body(carry, _):
            toks, caches, lens, hist = carry
            caches, lens, hist, nxt, preds, accept = self._spec_round(
                params, toks, caches, lens, hist)
            return (nxt, caches, lens, hist), (preds, accept)

        (_, caches, _, _), (preds, accepts) = jax.lax.scan(
            body, (tokens, caches, lengths, hist), None,
            length=self.block_size)
        return preds, accepts, caches

    def _block_spec_paged_impl(self, params, tokens, caches, lengths, hist,
                               tables):
        def body(carry, _):
            toks, caches, lens, hist = carry
            caches, lens, hist, nxt, preds, accept = self._spec_round(
                params, toks, caches, lens, hist, tables=tables)
            return (nxt, caches, lens, hist), (preds, accept)

        (_, caches, _, _), (preds, accepts) = jax.lax.scan(
            body, (tokens, caches, lengths, hist), None,
            length=self.block_size)
        return preds, accepts, caches

    @property
    def accepted_tokens_per_dispatch(self) -> float:
        """Mean tokens emitted per verify round (1.0 for the plain path)."""
        if self.verify_rounds == 0:
            return 1.0
        return self.accepted_tokens / self.verify_rounds

    @property
    def spec_compiles(self) -> Optional[int]:
        if not self.spec_k:
            return 0
        return _jit_cache_size(self._block_spec_paged if self.paged
                               else self._block_spec)

    @property
    def block_compiles(self) -> Optional[int]:
        return _jit_cache_size(self._block_paged if self.paged
                               else self._block)

    def step_block(self):
        """Advance every active stream by up to ``block_size`` tokens with
        ONE device dispatch and one host sync. Returns #active.

        Inactive slots decode garbage into their (about-to-be-overwritten)
        cache region; streams that hit their budget or the capacity wall
        mid-block have the surplus tokens discarded on the host — identical
        retirement semantics to ``step()``.

        The block runs in a ``prfaas.decode.block`` span; its phases in
        ``prfaas.decode.dispatch`` (host-to-device copies and the block
        call), ``.sync`` (tokens back on the host) and ``.bookkeep``
        (tokens, budgets, retirement)."""
        if not self.active.any():
            return 0
        with span("prfaas.decode.block"):
            if self.paged:
                self._ensure_pages()      # may retire page-starved slots
                if not self.active.any():
                    return 0
            if self.spec_k:
                return self._step_block_spec()
            return self._step_block_plain()

    def _step_block_plain(self):
        with span("prfaas.decode.dispatch") as dispatch:
            key = self._key
            step0 = jnp.int32(self._steps)
            self._blocks += 1
            self._steps += self.block_size
            if self.paged:
                tables = {"seq": jnp.asarray(self.table_seq),
                          "ring": jnp.asarray(self.table_ring)}
                toks, self.caches = self._block_paged(
                    self.params, jnp.asarray(self.tokens), self.caches,
                    jnp.asarray(self.lengths), key, step0, tables)
            else:
                toks, self.caches = self._block(
                    self.params, jnp.asarray(self.tokens),
                    self.caches, jnp.asarray(self.lengths), key, step0)
        with span("prfaas.decode.sync") as sync:
            toks = np.asarray(toks)                   # (block, num_slots)
        with span("prfaas.decode.bookkeep"):
            idx = np.where(self.active)[0]
            wall = sync.t1 - dispatch.t0
            self.decode_wall_s += wall
            self.slot_busy_s += len(idx) * wall
            if not self.paged:
                self._count_kv_tiles()
            # tokens a slot emits before retiring, exactly as step() would:
            # min(budget, room to capacity-1) per block — floored at 1
            # because step() appends once BEFORE its retirement check, so a
            # slot admitted at/over the capacity wall still emits one token
            valid = np.clip(
                np.minimum(self.budget[idx],
                           self.capacity - 1 - self.lengths[idx]),
                1, self.block_size).astype(int)
            self.tokens_out += int(valid.sum())
            self.lengths[idx] += valid
            self.budget[idx] -= valid
            self.tokens[idx] = toks[valid - 1, idx]
            done = (self.budget[idx] <= 0) | \
                   (self.lengths[idx] >= self.capacity - 1)
            for j, i in enumerate(idx):
                out = self.outputs[self.slot_req[i]].output_tokens
                out.extend(int(t) for t in toks[:valid[j], i])
                if done[j]:
                    self._retire(i)
        return int(self.active.sum())

    def _count_kv_tiles(self):
        """Add the block's decode-attention tiles to ``kv_tiles_*``: step
        ``i`` of the scan attends over ``lengths + 1 + i`` keys in every
        slot, active or not."""
        lens = self.lengths + self._block_steps
        for (S, kv_heads, dk, dv, itemsize), n in self._attn_calls:
            got, cap = tiles_fetched(np.minimum(lens, S), S, kv_heads=kv_heads,
                                     dk=dk, dv=dv, itemsize=itemsize)
            self.kv_tiles_fetched += n * got
            self.kv_tiles_capacity += n * cap

    def _step_block_spec(self):
        """Speculative ``step_block``: ``block_size`` draft/verify rounds in
        ONE dispatch, each emitting 1..spec_k+1 tokens per slot.  The host
        unpacks the per-round (predictions, accepts) into variable-stride
        budget/length/retire accounting.  A slot whose budget or capacity
        wall lands mid-stream takes only its valid prefix and retires, so
        the device-side history/length frontier stays authoritative exactly
        for the slots that continue."""
        with span("prfaas.decode.dispatch") as dispatch:
            self._blocks += 1
            toks = jnp.asarray(self.tokens)
            lens = jnp.asarray(self.lengths)
            hist = jnp.asarray(self._hist)
            if self.paged:
                tables = {"seq": jnp.asarray(self.table_seq),
                          "ring": jnp.asarray(self.table_ring)}
                preds, accepts, self.caches = self._block_spec_paged(
                    self.params, toks, self.caches, lens, hist, tables)
            else:
                preds, accepts, self.caches = self._block_spec(
                    self.params, toks, self.caches, lens, hist)
        with span("prfaas.decode.sync") as sync:
            preds = np.asarray(preds)    # (rounds, num_slots, spec_k + 1)
            accepts = np.asarray(accepts)  # (rounds, num_slots)
        with span("prfaas.decode.bookkeep"):
            idx = np.where(self.active)[0]
            wall = sync.t1 - dispatch.t0
            self.decode_wall_s += wall
            self.slot_busy_s += len(idx) * wall
            self.verify_rounds += int(accepts[:, idx].size)
            self.accepted_tokens += int((accepts[:, idx] + 1).sum())
            for i in idx:
                stream = np.concatenate(
                    [preds[r, i, :accepts[r, i] + 1]
                     for r in range(preds.shape[0])])
                valid = int(np.clip(
                    min(self.budget[i], self.capacity - 1 - self.lengths[i]),
                    1, len(stream)))
                take = stream[:valid]
                self.outputs[self.slot_req[i]].output_tokens.extend(
                    int(t) for t in take)
                L = int(self.lengths[i])
                hi = min(L + 1 + valid, self._hist.shape[1])
                self._hist[i, L + 1:hi] = take[:max(0, hi - (L + 1))]
                self.tokens[i] = take[-1]
                self.lengths[i] += valid
                self.budget[i] -= valid
                self.tokens_out += valid
                if self.budget[i] <= 0 or \
                        self.lengths[i] >= self.capacity - 1:
                    self._retire(int(i))
        return int(self.active.sum())

    def warmup_block(self):
        """Precompile the decode block program(s) on the live (zeroed or
        garbage) buffers: one throwaway dispatch with every slot inactive.
        Dense garbage writes land in regions a later admission fully
        rewrites; paged tables all point at the sink page.  After this the
        hot path never compiles again (``block_compiles`` /
        ``spec_compiles`` stay at 1)."""
        toks = jnp.zeros((self.num_slots,), jnp.int32)
        lens = jnp.zeros((self.num_slots,), jnp.int32)
        if self.paged:
            tables = {"seq": jnp.asarray(self.table_seq),
                      "ring": jnp.asarray(self.table_ring)}
            if self.spec_k:
                _, _, self.caches = self._block_spec_paged(
                    self.params, toks, self.caches, lens,
                    jnp.asarray(self._hist), tables)
            else:
                _, self.caches = self._block_paged(
                    self.params, toks, self.caches, lens, self._key,
                    jnp.int32(0), tables)
        else:
            if self.spec_k:
                _, _, self.caches = self._block_spec(
                    self.params, toks, self.caches, lens,
                    jnp.asarray(self._hist))
            else:
                _, self.caches = self._block(
                    self.params, toks, self.caches, lens, self._key,
                    jnp.int32(0))

    def run_until_drained(self, max_steps: int = 10_000):
        """Drain all active streams via ``step_block`` (``max_steps`` counts
        blocks)."""
        steps = 0
        while self.active.any() and steps < max_steps:
            self.step_block()
            steps += 1
        return steps


class RegionScheduler:
    """One continuously-batched loop per region: owns the prefill queue and
    the decode slot pool together (module doc has the state machine).

    ``submit`` enqueues a routed request, optionally naming which
    ``PrefillEngine`` runs it — deployments share one PrfaaS engine and one
    PD engine across regions, so the engine is per-request state, not
    per-scheduler.  ``tick()`` is one scheduler iteration:

      1. admit every READY request into free decode slots in one
         ``admit_many`` scatter — each tick IS a decode block boundary;
      2. advance ONE prefill unit: the next fixed-shape chunk of an
         in-flight ``ChunkedPrefill``, or a freshly formed same-(engine,
         bucket) FIFO batch run in a single bucketed ``prefill`` call;
      3. one ``step_block`` over all active decode slots.

    Finished units pass through ``on_unit_done`` (when set) so callers can
    do trim/wire/metrics accounting and hand back admit entries; the
    default trims each request's cache out of the bucket-padded batch.
    Starvation is impossible by construction — ``_admit`` runs FIFO at
    every boundary — and ``max_admit_wait`` (boundaries a request spent
    ready-but-unadmitted) makes that assertable instead of trusted.
    """

    def __init__(self, prefill: PrefillEngine, decode: DecodeEngine, *,
                 max_prefill_batch: int = 8, on_unit_done=None):
        self.prefill = prefill
        self.decode = decode
        self.max_prefill_batch = max(1, int(max_prefill_batch))
        self.on_unit_done = on_unit_done
        self.queue: deque = deque()          # (req, engine) — FIFO
        self.ready: deque = deque()          # (admit entry, ready boundary)
        self._inflight = None                # (ChunkedPrefill, reqs, lens)
        self.boundaries = 0                  # ticks == block boundaries
        self.max_admit_wait = 0
        self.starved_boundaries = 0          # ready waited w/ free slots
        self.wall_s = 0.0                    # scheduler makespan

    # ------------------------------------------------------------- intake
    def submit(self, req: Request, engine: Optional[PrefillEngine] = None):
        """Enqueue one routed request (state: queued)."""
        req.t_submit = time.perf_counter()
        self.queue.append((req, engine if engine is not None
                           else self.prefill))

    @property
    def has_work(self) -> bool:
        return bool(self.queue or self.ready or self._inflight is not None
                    or self.decode.active.any())

    # -------------------------------------------------------------- phases
    def _admit(self) -> int:
        """Block boundary: move ready -> decoding, as many as slots allow."""
        if not self.ready:
            return 0
        with span("prfaas.admit"):
            n = self.decode.admit_many([e for e, _ in self.ready])
            for _ in range(n):
                _, born = self.ready.popleft()
                self.max_admit_wait = max(self.max_admit_wait,
                                          self.boundaries - born)
            # the starvation guard: after a boundary admit, a request may
            # only remain ready because every slot is occupied
            if self.ready and self.decode.free_slots():
                self.starved_boundaries += 1
        return n

    def _finish_unit(self, engine, reqs, lengths, first, caches,
                     wall_s: float):
        if self.on_unit_done is not None:
            entries = self.on_unit_done(engine, reqs, lengths, first,
                                        caches, wall_s)
        else:
            entries = [(r, int(first[i]),
                        trim_request_cache(caches, i, int(lengths[i])),
                        int(lengths[i]))
                       for i, r in enumerate(reqs)]
        t = time.perf_counter()
        for r in reqs:
            r.t_first = t
        for e in entries:
            self.ready.append((e, self.boundaries))

    def _prefill_one(self):
        """Advance exactly one prefill unit: a chunk of the in-flight
        chunked prefill, or one bucketed batch from the queue head."""
        if self._inflight is not None:
            cp, reqs, lengths = self._inflight
            cp.step()
            if cp.done:
                self._inflight = None
                first, caches = cp.finish()
                self._finish_unit(cp.eng, reqs, lengths, first, caches,
                                  cp.wall_s)
            return
        if not self.queue:
            return
        req0, e0 = self.queue[0]
        pin = getattr(req0, "device_pin", None)
        if (pin is not None and pin.cached_len > 0
                and getattr(self.decode, "paged", False)):
            # device prefix hit: prefill only the uncached suffix, reading
            # the cached prefix straight out of the pinned pool pages
            self.queue.popleft()
            req0.t_unit_start = time.perf_counter()
            dec = self.decode
            prior = paged_mod.build_prior(
                dec.model.cfg, dec.caches, dec._layout, pin.seq_ids,
                None if pin.snapshot is None else pin.snapshot.payload,
                pin.cached_len, table_direct=True)
            lengths = np.array([len(req0.tokens)], np.int32)
            self._inflight = (e0.start_suffix(req0.tokens, prior,
                                              pin.cached_len),
                              [req0], lengths)
            self._prefill_one()              # run its first chunk this tick
            return
        if e0.is_chunked(len(req0.tokens)):
            # long prompt: becomes the chunk-interleaved unit (batch of 1 —
            # one fixed-shape chunk advances per tick, decode keeps running)
            self.queue.popleft()
            req0.t_unit_start = time.perf_counter()
            lengths = np.array([len(req0.tokens)], np.int32)
            toks = np.asarray(req0.tokens, np.int32)[None, :]
            self._inflight = (e0.start_chunked(toks, lengths), [req0],
                              lengths)
            self._prefill_one()              # run its first chunk this tick
            return
        # form one same-(engine, bucket) unit in FIFO order
        bucket = e0.bucket_for(len(req0.tokens))
        unit: List[Request] = []
        rest: deque = deque()
        while self.queue:
            r, e = self.queue.popleft()
            if (len(unit) < self.max_prefill_batch and e is e0
                    and not e.is_chunked(len(r.tokens))
                    and e.bucket_for(len(r.tokens)) == bucket):
                unit.append(r)
            else:
                rest.append((r, e))
        self.queue = rest
        t = time.perf_counter()
        for r in unit:
            r.t_unit_start = t
        lengths = np.array([len(r.tokens) for r in unit], np.int32)
        toks = np.zeros((len(unit), int(lengths.max())), np.int32)
        for i, r in enumerate(unit):
            toks[i, :len(r.tokens)] = r.tokens
        first, caches, wall = e0.prefill(toks, lengths)
        self._finish_unit(e0, unit, lengths, first, caches, wall)

    # ---------------------------------------------------------------- loop
    def tick(self):
        """One scheduler iteration: admit -> one prefill unit -> one decode
        block.  Returns #active decode slots after the block."""
        with span("prfaas.tick") as sp:
            self._admit()
            self._prefill_one()
            n = self.decode.step_block()
            self.boundaries += 1
        self.wall_s += sp.seconds
        return n

    def run(self, max_ticks: int = 100_000) -> int:
        """Tick until every submitted request has retired."""
        ticks = 0
        while self.has_work and ticks < max_ticks:
            self.tick()
            ticks += 1
        return ticks

    # ------------------------------------------------------------- metrics
    def occupancy(self) -> float:
        """Fraction of decode-slot-time occupied over the scheduler's own
        makespan (prefill gaps count against it — that is the point)."""
        denom = self.decode.num_slots * self.wall_s
        return self.decode.slot_busy_s / denom if denom > 0 else 0.0

    def goodput_tok_s(self) -> float:
        return (self.decode.tokens_out / self.wall_s
                if self.wall_s > 0 else 0.0)

    def stats(self) -> dict:
        return {"boundaries": self.boundaries,
                "max_admit_wait": self.max_admit_wait,
                "starved_boundaries": self.starved_boundaries,
                "occupancy": self.occupancy(),
                "goodput_tok_s": self.goodput_tok_s(),
                "tokens_out": self.decode.tokens_out,
                "truncations": self.decode.truncations,
                "accepted_tokens_per_dispatch":
                    self.decode.accepted_tokens_per_dispatch}


def slice_request_cache(caches, idx: int):
    """Extract request ``idx`` from a batched prefill cache -> batch of 1."""
    return jax.tree.map(lambda x: x[:, idx:idx + 1], caches)


def _strip_prior_pages(node):
    """Drop the table-direct prior operands (pool page leaves + block
    table) from a finished suffix prefill's caches, keeping the dense
    suffix rows and the ``off`` start marker."""
    if isinstance(node, dict):
        return {k: _strip_prior_pages(v) for k, v in node.items()
                if k not in ("pk", "pv", "tbl")}
    if isinstance(node, list):
        return [_strip_prior_pages(v) for v in node]
    return node


def trim_request_cache(caches, idx: int, length: int):
    """Extract request ``idx`` from a batched (bucket-padded) prefill cache
    and trim sequence-major leaves (k/v/ckv/kpe) to ``length`` — the bytes
    that actually need to cross the wire.  O(1) state leaves pass through.
    (Decoder-only caches; cross-attention caches keep their encoder len.)

    A block carrying an ``off`` marker (table-direct suffix prefill) holds
    only rows [off, length) in its seq leaves, so those trim to
    ``length - off``."""
    offs = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]:
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name == "off":
            offs[jax.tree_util.keystr(path[:-1])] = int(
                np.asarray(leaf).reshape(-1)[0])

    def cut(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        leaf = leaf[:, idx:idx + 1]
        if name in _SEQ_LEAVES and "cross" not in jax.tree_util.keystr(path):
            off = offs.get(jax.tree_util.keystr(path[:-1]), 0)
            leaf = leaf[:, :, :min(max(length - off, 0), leaf.shape[2])]
        return leaf

    return jax.tree_util.tree_map_with_path(cut, caches)
