"""Serving hot-path benchmark: pins the recompile-free engine wins in
``BENCH_engine.json`` so regressions fail ``benchmarks.run --smoke``.

Three measurements on a smoke model (harness overhead is exactly what the
tiny model exposes — the quantities below are scheduling tax, not FLOPs):

  * decode tokens/s at ``SLOTS`` active slots — the per-token loop
    (``DecodeEngine.step``: one dispatch + host sync + python bookkeeping
    per token) vs the blocked loop (``step_block``: ``lax.scan`` decode
    block on device, one sync per block).  Acceptance: >= 3x.
  * admission latency — K serial single-request full-cache
    ``dynamic_update_slice`` placements (the old path, reconstructed here)
    vs one batched ``admit_many`` scatter.
  * prefill compile stability — warm the (batch, length) buckets, then run
    a mixed-length workload and count recompiles.  Acceptance: 0.
  * decode-slot occupancy + goodput at ``SLOTS`` slots under a mixed
    long-prefill + decode load — the PR 5 alternating loop (whole-batch
    prefill, admit waves, drain to empty) vs the continuous
    ``RegionScheduler`` (bucket-exact units, chunk interleave, admission at
    block boundaries).  Acceptance: continuous occupancy strictly above the
    alternating baseline, with 0 recompiles after the warm run.
  * speculative decode (PR 10) — n-gram-drafted multi-token decode on the
    continuous scheduler, k swept against the plain k=0 path on the same
    refilling workload.  Acceptance: some k >= 2 beats plain tokens/s,
    accepted_tokens_per_dispatch > 1.0, token streams identical to k=0,
    one verify compile per draft depth.
  * paged KV (PR 7) — (a) admission latency of the paged page-write
    scatter vs the dense full-slot placement, with 0 admission recompiles
    after ``warmup_admission``; (b) prefix-hit suffix-only prefill at a
    50% hit rate: measured prefilled-token savings plus the analytic
    prefill-FLOP savings, with 0 decode-block recompiles; (c) resident-KV
    headroom — device bytes held by LRU-resident (reusable, reclaimable)
    prefix pages after the workload drains, a capacity the dense layout
    has no counterpart for.

    PYTHONPATH=src python -m benchmarks.engine_bench [--smoke]
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, write_json
from repro.configs import get_smoke_config
from repro.configs.base import AttentionSpec
from repro.core.blockpool import BlockPool
from repro.core.hardware import CHIPS, AnalyticProfile
from repro.core.prefix_cache import HybridPrefixCache
from repro.models import Model, paged_layout, prepare_decode_caches
from repro.serving.api import PagePin, Request
from repro.serving.engine import (DecodeEngine, PrefillEngine,
                                  RegionScheduler, trim_request_cache)

# One KV-cache attention arch (SWA; windowed cache decode) and one
# linear-state arch
# (O(1) recurrent states) — the two regimes of the serving hot path.  The
# headline decode number is the linear-state row: on this CPU container the
# attention smoke model's XLA op-execution floor inside the decode block
# (~0.45ms/token of real compute) caps its measurable speedup near 3x,
# whereas on an accelerator the per-token loop's host tax dominates both.
ARCH_ATTN = "h2o-danube-1.8b"
ARCH_LINEAR = "xlstm-350m"
ARCH_PAGED = "mistral-nemo-12b"     # full attention: seq pages stay resident
SLOTS = 16
CAPACITY = 192
PROMPT_LEN = 24
BLOCK = 16
PAGE = 16


def _mk_requests(cfg, n, max_new, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    tokens=rng.integers(0, cfg.vocab_size,
                                        (PROMPT_LEN,)).astype(np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


def bench_decode(tag, model, params, entries, max_new):
    """tokens/s of the per-token loop vs the blocked loop, same workload."""
    eng = DecodeEngine(model, params, SLOTS, CAPACITY, block_size=BLOCK)
    # warm both compiled paths (admission, step, block) out of the timing
    eng.admit_many(entries)
    eng.step()
    eng.run_until_drained()

    def timed(loop, reps=5):
        # best-of-reps: each rep re-admits the same workload and drains it
        produced = sum(r.max_new_tokens for r, *_ in entries)
        best = float("inf")
        for _ in range(reps):
            eng.admit_many(entries)
            t0 = time.perf_counter()
            loop()
            best = min(best, time.perf_counter() - t0)
        return produced / best, best

    def per_token():
        while eng.active.any():
            eng.step()

    tok_s_step, wall_step = timed(per_token)
    tok_s_block, wall_block = timed(eng.run_until_drained)
    speedup = tok_s_block / tok_s_step
    emit(f"engine/decode_per_token_{tag}", wall_step * 1e6,
         f"{tok_s_step:.1f}tok/s slots={SLOTS}")
    emit(f"engine/decode_block_{tag}", wall_block * 1e6,
         f"{tok_s_block:.1f}tok/s block={BLOCK} speedup={speedup:.2f}x")
    assert speedup > 1.0, (
        f"blocked decode slower than per-token loop ({speedup:.2f}x)")
    return {"slots": SLOTS, "block_size": BLOCK, "new_tokens": max_new,
            "per_token_tok_s": round(tok_s_step, 1),
            "block_tok_s": round(tok_s_block, 1),
            "speedup": round(speedup, 2),
            "block_compiles": eng.block_compiles}


def bench_admission(model, params, entries):
    """K serial full-cache placements (legacy) vs one batched scatter."""
    K = len(entries)
    eng = DecodeEngine(model, params, SLOTS, CAPACITY, block_size=BLOCK)

    # the old DecodeEngine._place: one jit'd full-cache update per request
    def place_one(caches, one_cache, slot):
        def put(buf, new):
            idx = (0, slot) + (0,) * (buf.ndim - 2)
            return jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype),
                                                idx)
        return jax.tree.map(put, caches, one_cache)

    serial_place = jax.jit(place_one, donate_argnums=(0,))

    def serial(caches):
        # the old admit() loop: per request, prepare + one jit'd full-cache
        # update (admit_many does the same prepare, then ONE placement call)
        for slot, (_, _, c, _) in enumerate(entries):
            p = prepare_decode_caches(model.cfg, c, CAPACITY)
            caches = serial_place(caches, p, jnp.int32(slot))
        jax.block_until_ready(jax.tree.leaves(caches)[0])
        return caches

    caches = eng.caches
    caches = serial(caches)                       # warm
    t0 = time.perf_counter()
    caches = serial(caches)
    serial_s = time.perf_counter() - t0
    eng.caches = caches

    eng.admit_many(entries)                       # warm batched path
    eng.run_until_drained(max_steps=0)
    for slot in range(SLOTS):                     # reset slot state
        if eng.active[slot]:
            eng.active[slot] = False
            eng.slot_req[slot] = None
    eng._free.clear()
    eng._free.extend(range(SLOTS))
    eng.outputs.clear()
    t0 = time.perf_counter()
    eng.admit_many(entries)
    jax.block_until_ready(jax.tree.leaves(eng.caches)[0])
    batched_s = time.perf_counter() - t0
    speedup = serial_s / batched_s
    emit("engine/admit_serial", serial_s * 1e6, f"K={K} full-cache updates")
    emit("engine/admit_batched", batched_s * 1e6,
         f"K={K} one scatter, speedup={speedup:.2f}x")
    return {"K": K, "serial_us": round(serial_s * 1e6, 1),
            "batched_us": round(batched_s * 1e6, 1),
            "speedup": round(speedup, 2)}


def bench_prefill_buckets(model, params, cfg, smoke):
    """Mixed-length workload after bucket warmup must not recompile."""
    eng = PrefillEngine(model, params, min_bucket=32)
    rng = np.random.default_rng(1)
    batch, buckets = 4, (32, 64, 128, 256)
    eng.warmup([batch], buckets)
    warm_compiles = eng.compiles
    n_batches = 4 if smoke else 12
    walls = []
    for _ in range(n_batches):
        lens = rng.integers(9, 256, (batch,))
        toks = np.zeros((batch, int(lens.max())), np.int32)
        for i, L in enumerate(lens):
            toks[i, :L] = rng.integers(0, cfg.vocab_size, (L,))
        t0 = time.perf_counter()
        eng.prefill(toks, lens.astype(np.int32))
        walls.append(time.perf_counter() - t0)
    recompiles = eng.compiles - warm_compiles
    emit("engine/prefill_recompiles", float(np.mean(walls)) * 1e6,
         f"{recompiles} recompiles over {n_batches} mixed-length batches "
         f"(warmup={warm_compiles} compiles)")
    assert recompiles == 0, (
        f"{recompiles} prefill recompiles after bucket warmup")
    return {"batch": batch, "buckets": list(buckets),
            "warmup_compiles": warm_compiles,
            "recompiles_after_warmup": recompiles,
            "mixed_batches": n_batches,
            "prefill_mean_us": round(float(np.mean(walls)) * 1e6, 1)}


LONG_LEN = 200          # past the occupancy bench's max_bucket -> chunked


def _reset_decode(dec: DecodeEngine):
    """Return a DecodeEngine to its post-init state without re-jitting."""
    dec.lengths[:] = 0
    dec.tokens[:] = 0
    dec.active[:] = False
    dec.budget[:] = 0
    dec.slot_req = [None] * dec.num_slots
    dec.outputs = {}
    dec.truncations = 0
    dec.decode_wall_s = dec.slot_busy_s = 0.0
    dec.tokens_out = 0
    dec._free.clear()
    dec._free.extend(range(dec.num_slots))


def bench_occupancy(model, params, cfg, smoke):
    """Occupancy/goodput at SLOTS decode slots, mixed long-prefill + decode
    load: the alternating loop pays one whole-batch prefill (every prompt
    padded to the global max) with decode idle, then drains to empty
    between admit waves — with more requests than slots and ragged decode
    budgets, slots sit idle while each wave's longest stream finishes; the
    scheduler runs bucket-exact units, chunk-interleaves long prompts
    between decode blocks, and refills freed slots at the next boundary."""
    capacity = 384
    n_short, n_long = (20, 4) if smoke else (28, 8)
    hi_new = 48 if smoke else 96
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i,
                    tokens=rng.integers(
                        0, cfg.vocab_size,
                        (PROMPT_LEN if i < n_short else LONG_LEN,)
                    ).astype(np.int32),
                    max_new_tokens=int(rng.integers(8, hi_new + 1)))
            for i in range(n_short + n_long)]
    reqs = [reqs[i] for i in rng.permutation(len(reqs))]   # arrival mix
    peng = PrefillEngine(model, params, min_bucket=32, max_bucket=64)
    peng.warmup([1, 8], [PROMPT_LEN, LONG_LEN])
    dec = DecodeEngine(model, params, SLOTS, capacity, block_size=BLOCK)

    def alternating():
        # faithful PR 5 regime: ONE bucketed prefill call for the whole
        # batch (padded to the longest prompt's chunk multiple), then admit
        # waves that drain all active streams before admitting the rest
        lengths = np.array([len(r.tokens) for r in reqs], np.int32)
        toks = np.zeros((len(reqs), int(lengths.max())), np.int32)
        for i, r in enumerate(reqs):
            toks[i, :len(r.tokens)] = r.tokens
        first, caches, _ = peng.prefill(toks, lengths)
        pending = [(r, int(first[i]),
                    trim_request_cache(caches, i, int(lengths[i])),
                    int(lengths[i])) for i, r in enumerate(reqs)]
        while pending:
            n = dec.admit_many(pending)
            pending = pending[n:]
            dec.run_until_drained()

    def continuous():
        sched = RegionScheduler(peng, dec, max_prefill_batch=8)
        for r in reqs:
            sched.submit(r)
        sched.run()

    def timed(fn, reps=2):
        best = (0.0, float("inf"), 0)          # (occupancy, wall, tokens)
        for _ in range(reps):
            _reset_decode(dec)
            t0 = time.perf_counter()
            fn()
            wall = time.perf_counter() - t0
            occ = dec.slot_busy_s / (SLOTS * wall)
            if occ > best[0]:
                best = (occ, wall, dec.tokens_out)
        return best

    # warm run of each regime compiles its batch shapes out of the timing
    _reset_decode(dec)
    alternating()
    _reset_decode(dec)
    continuous()
    warm_compiles = peng.compiles
    alt_occ, alt_wall, alt_toks = timed(alternating)
    con_occ, con_wall, con_toks = timed(continuous)
    recompiles = peng.compiles - warm_compiles
    alt_good, con_good = alt_toks / alt_wall, con_toks / con_wall
    emit("engine/occupancy_alternating", alt_wall * 1e6,
         f"occ={alt_occ:.3f} {alt_good:.1f}tok/s slots={SLOTS}")
    emit("engine/occupancy_continuous", con_wall * 1e6,
         f"occ={con_occ:.3f} {con_good:.1f}tok/s "
         f"gain={con_occ / max(alt_occ, 1e-9):.2f}x")
    assert con_occ > alt_occ, (
        f"continuous scheduler occupancy {con_occ:.3f} not above "
        f"alternating baseline {alt_occ:.3f}")
    assert recompiles == 0, (
        f"{recompiles} prefill recompiles during occupancy bench")
    assert dec.block_compiles in (None, 1), (
        f"decode block recompiled: {dec.block_compiles}")
    return {"slots": SLOTS, "block_size": BLOCK, "capacity": capacity,
            "requests": len(reqs), "long_prompts": n_long,
            "long_len": LONG_LEN, "new_tokens_hi": hi_new,
            "occupancy_continuous": round(con_occ, 4),
            "occupancy_alternating": round(alt_occ, 4),
            "goodput_tok_s_continuous": round(con_good, 1),
            "goodput_tok_s_alternating": round(alt_good, 1),
            "recompiles_after_warmup": recompiles}


SPEC_CAPACITY = 640     # speculative bench KV capacity (long streams so the
SPEC_BLOCK = 16         # n-gram drafter has history to mine)


def bench_spec_decode(model, params, cfg, smoke):
    """Speculative multi-token decode (PR 10 tentpole) at SLOTS slots on the
    continuous scheduler: per-slot n-gram drafts verified k+1-at-a-time in
    one dispatch, greedy acceptance, variable tokens-per-block.  Sweeps
    draft depth k against the k=0 plain path on the SAME workload
    (requests >> slots, so freed slots refill at block boundaries — the
    honest occupancy regime, no drain-tail artifact).  Acceptance: some
    k >= 2 beats plain tokens/s with accepted_tokens_per_dispatch > 1.0,
    token streams identical to k=0, and one verify compile per k."""
    new_tok, n_req, reps = (384, 24, 2) if smoke else (512, 32, 3)
    ks = (0, 2) if smoke else (0, 2, 3)
    rng0 = np.random.default_rng(11)
    prompts = [rng0.integers(0, cfg.vocab_size,
                             (PROMPT_LEN,)).astype(np.int32)
               for _ in range(n_req)]

    def mk():
        return [Request(rid=i, tokens=prompts[i], max_new_tokens=new_tok)
                for i in range(n_req)]

    sweep, outs = {}, {}
    for k in ks:
        peng = PrefillEngine(model, params, min_bucket=32, max_bucket=64)
        dec = DecodeEngine(model, params, SLOTS, SPEC_CAPACITY,
                           block_size=SPEC_BLOCK, spec_k=k, spec_ngram=1)
        sched = RegionScheduler(peng, dec, max_prefill_batch=4)
        for r in mk():
            sched.submit(r)
        sched.run()                         # warm run compiles everything
        outs[k] = {rid: r.output_tokens for rid, r in dec.outputs.items()}
        warm_spec = dec.spec_compiles
        best = float("inf")
        for _ in range(reps):
            dec.outputs.clear()
            dec.tokens_out = 0
            sched = RegionScheduler(peng, dec, max_prefill_batch=4)
            for r in mk():
                sched.submit(r)
            t0 = time.perf_counter()
            sched.run()
            best = min(best, time.perf_counter() - t0)
        produced = n_req * new_tok
        acc = dec.accepted_tokens_per_dispatch
        recompiles = dec.spec_compiles - warm_spec
        assert recompiles == 0, (
            f"k={k}: {recompiles} verify recompiles after warm run")
        sweep[f"k{k}"] = {
            "tok_s": round(produced / best, 1),
            "accepted_tokens_per_dispatch": round(acc, 3),
            "verify_compiles": dec.spec_compiles,
        }
        emit(f"engine/spec_decode_k{k}", best * 1e6,
             f"{produced / best:.1f}tok/s acc/disp={acc:.2f} slots={SLOTS}")

    plain = sweep["k0"]["tok_s"]
    best_k, best_ratio = 0, 1.0
    for k in ks[1:]:
        assert outs[k] == outs[0], (
            f"k={k} speculative tokens diverge from plain greedy")
        r = sweep[f"k{k}"]["tok_s"] / plain
        sweep[f"k{k}"]["speedup_vs_plain"] = round(r, 3)
        if r > best_ratio:
            best_k, best_ratio = k, r
    assert best_k >= 2, (
        f"no draft depth beat plain decode (best ratio {best_ratio:.3f})")
    assert sweep[f"k{best_k}"]["accepted_tokens_per_dispatch"] > 1.0
    emit("engine/spec_decode_speedup", best_ratio,
         f"best k={best_k} vs plain, token-identical")
    return {"slots": SLOTS, "capacity": SPEC_CAPACITY,
            "block_size": SPEC_BLOCK, "requests": n_req,
            "new_tokens": new_tok, "spec_ngram": 1,
            "best_k": best_k, "speedup_vs_plain": round(best_ratio, 3),
            "accepted_tokens_per_dispatch":
                sweep[f"k{best_k}"]["accepted_tokens_per_dispatch"],
            "sweep": sweep}


def bench_paged_admission(model, params, entries):
    """Paged page-write admission scatter vs the dense full-slot placement,
    same prefilled entries.  The paged path must run recompile-free after
    ``warmup_admission`` on the same traffic shape."""
    def timed_admit(eng, reps=5):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            eng.admit_many(entries)
            jax.block_until_ready(jax.tree.leaves(eng.caches)[0])
            best = min(best, time.perf_counter() - t0)
            for slot in range(SLOTS):
                if eng.active[slot]:
                    eng._retire(slot)
            eng.outputs.clear()
        return best

    dense = DecodeEngine(model, params, SLOTS, CAPACITY, block_size=BLOCK)
    timed_admit(dense, reps=1)                    # warm the dense scatter
    dense_s = timed_admit(dense)

    dec = DecodeEngine(model, params, SLOTS, CAPACITY, block_size=BLOCK,
                       paged=True, page_tokens=PAGE)
    dec.warmup_admission([SLOTS], [PROMPT_LEN])
    warm = dec.admit_compiles
    paged_s = timed_admit(dec)
    recompiles = dec.admit_compiles - warm
    speedup = dense_s / paged_s
    emit("engine/admit_dense_layout", dense_s * 1e6,
         f"K={len(entries)} full-slot placement")
    emit("engine/admit_paged_layout", paged_s * 1e6,
         f"K={len(entries)} page scatter, vs dense={speedup:.2f}x, "
         f"{recompiles} recompiles")
    assert recompiles == 0, (
        f"{recompiles} paged-admission recompiles after warmup_admission")
    s = dec.pool.stats
    assert s["allocated"] == s["freed"] + s["evicted"] + dec.pool.resident

    # int8 wire admission (PR 8): the quantized pytree admits directly,
    # dequantization fused into the page scatter — same recompile-free
    # contract after warmup_admission warms the wire program variant
    from repro.models.kvcache import quantize_cache_for_wire
    wdec = DecodeEngine(model, params, SLOTS, CAPACITY, block_size=BLOCK,
                        paged=True, page_tokens=PAGE)
    wdec.wire_admission = True
    wdec.warmup_admission([SLOTS], [PROMPT_LEN])
    warm_w = wdec.admit_compiles
    wire_entries = [(r, f, quantize_cache_for_wire(c)[0], L)
                    for (r, f, c, L) in entries]

    def timed_wire(reps=5):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            wdec.admit_many(wire_entries)
            jax.block_until_ready(jax.tree.leaves(wdec.caches)[0])
            best = min(best, time.perf_counter() - t0)
            for slot in range(SLOTS):
                if wdec.active[slot]:
                    wdec._retire(slot)
            wdec.outputs.clear()
        return best

    wire_s = timed_wire()
    wire_recompiles = wdec.admit_compiles - warm_w
    emit("engine/admit_wire_paged", wire_s * 1e6,
         f"K={len(entries)} int8 dequant-in-scatter, "
         f"{wire_recompiles} recompiles")
    assert wire_recompiles == 0, (
        f"{wire_recompiles} wire-admission recompiles after "
        "warmup_admission")
    return {"K": len(entries), "dense_us": round(dense_s * 1e6, 1),
            "paged_us": round(paged_s * 1e6, 1),
            "speedup_vs_dense": round(speedup, 2),
            "admit_warmup_compiles": warm,
            "admit_recompiles_after_warmup": recompiles,
            "wire_admit_us": round(wire_s * 1e6, 1),
            "wire_admit_warmup_compiles": warm_w,
            "wire_admit_recompiles_after_warmup": wire_recompiles}


def bench_paged_prefix(model, params, cfg, smoke):
    """Suffix-only prefill at a 50% prefix-hit rate: half the workload
    shares a registered 64-token prefix and resumes from its device pages,
    so only the suffix is prefilled.  Reports the measured prefilled-token
    savings, the analytic prefill-FLOP savings (incremental
    ``prefill_flops(L) - prefill_flops(c)`` charge per hit), and the
    resident-KV headroom the paged pool retains after the drain."""
    capacity = 192
    lay = paged_layout(cfg, capacity, PAGE, 1)
    has_state = any(not isinstance(b.mixer, AttentionSpec)
                    for g in cfg.groups for b in g.blocks)
    pool = BlockPool(SLOTS * capacity // PAGE, PAGE)
    cache = HybridPrefixCache(pool, 0, 1, has_full_attn=lay.seq_cols > 0,
                              has_linear=lay.ring_cols > 0 or has_state)
    peng = PrefillEngine(model, params, min_bucket=32, max_bucket=64)
    dec = DecodeEngine(model, params, SLOTS, capacity, block_size=BLOCK,
                       paged=True, pool=pool, page_tokens=PAGE)
    dec.on_admit = lambda req, L, ids, snap: cache.insert_device(
        [int(t) for t in req.tokens], ids, snap)
    sched = RegionScheduler(peng, dec, max_prefill_batch=8)

    rng = np.random.default_rng(7)
    c_len, total_len = 64, 128
    prefix = rng.integers(0, cfg.vocab_size, (c_len,)).astype(np.int32)
    sched.submit(Request(rid=999, tokens=prefix, max_new_tokens=2))
    sched.run()                        # registers the prefix pages
    blocks_warm = dec.block_compiles

    n = 8 if smoke else 12
    reqs = []
    for i in range(n):
        rest = rng.integers(0, cfg.vocab_size,
                            (total_len - c_len,)).astype(np.int32)
        if i % 2 == 0:                 # 50% of the workload hits
            toks = np.concatenate([prefix, rest])
            c, ids, snap = cache.match_resume([int(t) for t in toks])
            assert c == c_len, "registered prefix must be resumable"
            pool.retain(ids)
            reqs.append(Request(rid=i, tokens=toks, max_new_tokens=8,
                                device_pin=PagePin(c, ids, snap)))
        else:
            cold = rng.integers(0, cfg.vocab_size,
                                (c_len,)).astype(np.int32)
            reqs.append(Request(rid=i, tokens=np.concatenate([cold, rest]),
                                max_new_tokens=8))
    before = peng.tokens_prefilled
    t0 = time.perf_counter()
    for r in reqs:
        sched.submit(r)
    sched.run()
    wall = time.perf_counter() - t0
    prefilled = peng.tokens_prefilled - before
    total = sum(len(r.tokens) for r in reqs)
    assert prefilled == total - (n // 2) * c_len, (
        "prefix hits must prefill only the uncached suffix")
    token_savings = 1.0 - prefilled / total

    profile = AnalyticProfile(cfg, CHIPS["h200"], 8)
    f_full = profile.prefill_flops(total_len)
    f_inc = f_full - profile.prefill_flops(c_len)
    flop_savings = 1.0 - ((n // 2) * f_inc + (n - n // 2) * f_full) \
        / (n * f_full)

    decode_recompiles = dec.block_compiles - blocks_warm
    s = pool.stats
    assert s["allocated"] == s["freed"] + s["evicted"] + pool.resident
    resident_bytes = pool.resident * dec.page_bytes
    emit("engine/paged_prefix_hits", wall * 1e6,
         f"n={n} hit_rate=0.5 token_savings={token_savings:.3f} "
         f"flop_savings={flop_savings:.3f}")
    emit("engine/paged_resident_kv", float(resident_bytes),
         f"{pool.resident}/{pool.num_blocks} pages resident after drain, "
         f"{decode_recompiles} decode recompiles")
    assert decode_recompiles == 0, (
        f"{decode_recompiles} paged decode-block recompiles after warm run")
    assert resident_bytes > 0, "registered prefix pages must stay resident"
    return {"requests": n, "hit_rate": 0.5, "prompt_len": total_len,
            "cached_len": c_len,
            "tokens_prefilled": int(prefilled),
            "tokens_submitted": int(total),
            "token_savings_frac": round(token_savings, 4),
            "flop_savings_frac": round(flop_savings, 4),
            "decode_recompiles": decode_recompiles,
            "resident_kv_bytes": int(resident_bytes),
            "resident_pages": pool.resident,
            "pool_pages": pool.num_blocks,
            "wall_us": round(wall * 1e6, 1)}


def _setup(cfg, max_new):
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _mk_requests(cfg, SLOTS, max_new)
    peng = PrefillEngine(model, params, min_bucket=32)
    toks = np.stack([r.tokens for r in reqs])
    lens = np.full((SLOTS,), PROMPT_LEN, np.int32)
    first, caches, _ = peng.prefill(toks, lens)
    entries = [(r, int(first[i]), trim_request_cache(caches, i, PROMPT_LEN),
                PROMPT_LEN) for i, r in enumerate(reqs)]
    return cfg, model, params, entries


def main(smoke: bool = False, out_path: str = "BENCH_engine.json"):
    max_new = 32 if smoke else 64
    cfg_a, model_a, params_a, entries_a = _setup(get_smoke_config(ARCH_ATTN),
                                                 max_new)
    cfg_l, model_l, params_l, entries_l = _setup(
        get_smoke_config(ARCH_LINEAR), max_new)
    decode = {
        "linear_state": bench_decode("linear", model_l, params_l, entries_l,
                                     max_new),
        "attention": bench_decode("attn", model_a, params_a, entries_a,
                                  max_new),
    }
    admission = bench_admission(model_l, params_l, entries_l)
    prefill = bench_prefill_buckets(model_a, params_a, cfg_a, smoke)
    occupancy = bench_occupancy(model_a, params_a, cfg_a, smoke)
    cfg_p, model_p, params_p, entries_p = _setup(get_smoke_config(ARCH_PAGED),
                                                 max_new)
    paged = {
        "admission": bench_paged_admission(model_p, params_p, entries_p),
        "prefix": bench_paged_prefix(model_p, params_p, cfg_p, smoke),
    }
    speculative = bench_spec_decode(model_p, params_p, cfg_p, smoke)
    write_json(out_path, {
        "archs": {"linear_state": ARCH_LINEAR, "attention": ARCH_ATTN,
                  "paged": ARCH_PAGED},
        "smoke": smoke, "backend": jax.default_backend(),
        # headline: block-decode speedup at SLOTS active slots vs the
        # per-token loop (linear-state regime; see module docstring)
        "decode_speedup_at_16_slots": decode["linear_state"]["speedup"],
        # headline: continuous-scheduler decode-slot occupancy vs the
        # alternating-loop baseline, same mixed load at SLOTS slots
        "occupancy_at_16_slots": occupancy["occupancy_continuous"],
        "occupancy_alternating_baseline":
            occupancy["occupancy_alternating"],
        # headline: measured prefilled-token savings from device-resident
        # prefix pages at a 50% hit rate, and the KV bytes those resident
        # pages keep reusable after the workload drains
        "paged_token_savings_at_50pct_hits":
            paged["prefix"]["token_savings_frac"],
        "paged_resident_kv_bytes": paged["prefix"]["resident_kv_bytes"],
        # headline: speculative decode vs plain at SLOTS slots on the
        # continuous scheduler, greedy token-identical, and the mean
        # tokens each verify dispatch emitted at the best draft depth
        "spec_decode_speedup_at_16_slots": speculative["speedup_vs_plain"],
        "accepted_tokens_per_dispatch":
            speculative["accepted_tokens_per_dispatch"],
        "decode": decode, "admission": admission, "prefill": prefill,
        "occupancy": occupancy, "paged": paged, "speculative": speculative,
    })
    return True


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    main(smoke=args.smoke)
