"""Serving hot-path tests: bucketed/chunked prefill exactness and compile
stability, blocked decode equivalence, batched admission, free-slot deque,
truncation accounting.

Marked ``slow`` (they jit real smoke models); the compile-count guards are
the load-bearing ones — they pin the recompile-free property the ISSUE-5
refactor exists for.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import Model
from repro.serving.api import Request
from repro.serving.engine import (DecodeEngine, PrefillEngine, next_pow2,
                                  trim_request_cache)

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def kimi():
    """Hybrid smoke model (KDA conv + MLA): the hardest cache layout."""
    cfg = get_smoke_config("kimi-linear-1t")
    model = Model(cfg, use_kernels=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def danube():
    """Full-attention smoke model."""
    cfg = get_smoke_config("h2o-danube-1.8b")
    model = Model(cfg, use_kernels=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    toks = np.zeros((len(lens), max(lens)), np.int32)
    for i, L in enumerate(lens):
        toks[i, :L] = rng.integers(0, cfg.vocab_size, (L,))
    return toks, np.asarray(lens, np.int32)


class TestPrefillBuckets:
    def test_bucket_padding_is_exact(self, kimi):
        """A short prompt padded into a larger bucket must produce the same
        first token and (trimmed) cache as an unpadded prefill — including
        linear-mixer states and the conv window."""
        cfg, model, params = kimi
        toks, lens = _prompts(cfg, [45])
        ref_first, ref_caches = jax.jit(model.prefill)(
            params, {"tokens": jnp.asarray(toks)})
        eng = PrefillEngine(model, params, min_bucket=32)
        first, caches, _ = eng.prefill(toks, lens)
        assert int(first[0]) == int(jnp.argmax(ref_first[0]))
        got = trim_request_cache(caches, 0, 45)
        want = trim_request_cache(ref_caches, 0, 45)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=1e-4)

    def test_one_compile_per_bucket(self, danube):
        cfg, model, params = danube
        eng = PrefillEngine(model, params, min_bucket=32)
        toks, lens = _prompts(cfg, [33, 40, 50, 60])
        eng.prefill(toks, lens)
        after_first = eng.compiles
        # same (batch, length) bucket, different raw lengths: NO new compile
        for lens2 in ([34, 61, 64, 35], [50, 50, 50, 50]):
            toks2, l2 = _prompts(cfg, lens2, seed=3)
            eng.prefill(toks2, l2)
        assert eng.compiles == after_first
        # a new bucket compiles exactly once more
        toks3, l3 = _prompts(cfg, [100, 120, 90, 70], seed=4)
        eng.prefill(toks3, l3)
        assert eng.compiles == after_first + 1

    def test_warmup_then_zero_recompiles(self, danube):
        cfg, model, params = danube
        eng = PrefillEngine(model, params, min_bucket=32)
        eng.warmup([2], [32, 64, 128])
        warm = eng.compiles
        rng = np.random.default_rng(7)
        for _ in range(5):
            lens = rng.integers(9, 128, (2,)).tolist()
            toks, l = _prompts(cfg, lens, seed=int(rng.integers(1 << 30)))
            eng.prefill(toks, l)
        assert eng.compiles == warm

    def test_warmup_covers_chunked_prompts(self, danube):
        """Warmup lengths past max_bucket pre-trace the chunk programs for
        their exact chunk count, so serving a past-max-bucket prompt later
        never recompiles (the PR 6 chunk-interleaving hot path)."""
        cfg, model, params = danube
        eng = PrefillEngine(model, params, min_bucket=32, max_bucket=64)
        # 300 -> ceil(300/64)=5 chunks: warms every chunk index 0..4, which
        # also covers any shorter chunked prompt (fewer chunks, same shapes)
        eng.warmup([2], [32, 64, 300])
        warm = eng.compiles
        rng = np.random.default_rng(11)
        for _ in range(4):
            lens = rng.integers(9, 300, (2,)).tolist()
            toks, l = _prompts(cfg, lens, seed=int(rng.integers(1 << 30)))
            eng.prefill(toks, l)
        assert eng.compiles == warm

    # kimi = KDA conv + MLA latents; qwen = plain GQA; danube = SWA with a
    # 64-token window, so chunk-2 queries straddle the band across the
    # chunk boundary (the q_offset + window path in gqa_forward_chunk)
    @pytest.mark.parametrize(
        "arch", ["kimi-linear-1t", "qwen2.5-3b", "h2o-danube-1.8b"])
    def test_chunked_prefill_matches_full(self, arch):
        """Prompts past max_bucket run as fixed-shape chunks and must match
        the one-shot prefill (logits + valid cache region)."""
        cfg = get_smoke_config(arch)
        model = Model(cfg, use_kernels=False)
        params = model.init(jax.random.PRNGKey(0))
        toks, lens = _prompts(cfg, [150, 100], seed=2)
        full = PrefillEngine(model, params, min_bucket=32)
        chunked = PrefillEngine(model, params, min_bucket=32, max_bucket=64)
        f_first, f_caches, _ = full.prefill(toks, lens)
        c_first, c_caches, _ = chunked.prefill(toks, lens)
        np.testing.assert_array_equal(f_first, c_first)
        for i, L in enumerate(lens):
            want = trim_request_cache(f_caches, i, int(L))
            got = trim_request_cache(c_caches, i, int(L))
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
                np.testing.assert_allclose(np.asarray(a, np.float32),
                                           np.asarray(b, np.float32),
                                           atol=1e-3)

    def test_next_pow2(self):
        assert [next_pow2(n) for n in (1, 2, 3, 8, 9)] == [1, 2, 4, 8, 16]
        assert next_pow2(5, lo=32) == 32


def _admit_all(eng, cfg, model, params, lens, max_new, seed=0):
    peng = PrefillEngine(model, params, min_bucket=32)
    toks, l = _prompts(cfg, lens, seed=seed)
    first, caches, _ = peng.prefill(toks, l)
    entries = [
        (Request(rid=i, tokens=toks[i, :L], max_new_tokens=max_new),
         int(first[i]), trim_request_cache(caches, i, int(L)), int(L))
        for i, L in enumerate(lens)]
    return entries, eng.admit_many(entries)


class TestDecodeBlock:
    def test_block_matches_per_token(self, kimi):
        cfg, model, params = kimi
        lens = [16, 24, 33, 40]
        a = DecodeEngine(model, params, 4, 128, block_size=4)
        b = DecodeEngine(model, params, 4, 128, block_size=4)
        _admit_all(a, cfg, model, params, lens, max_new=6)
        _admit_all(b, cfg, model, params, lens, max_new=6)
        while a.active.any():
            a.step()                       # per-token loop
        b.run_until_drained()              # blocked loop
        for i in range(4):
            assert a.outputs[i].output_tokens == b.outputs[i].output_tokens
            assert b.outputs[i].finished and not b.outputs[i].truncated

    def test_kv_tile_counter(self, danube, monkeypatch):
        """A dense block adds, for every attention layer and scan step, the
        KV tiles the decode-attention kernel copies for each slot (step
        ``i`` attends over ``lengths + i`` keys, at most the ring's 64) and
        the tiles of the whole capacity.  16-key tiles (a smaller tile
        budget) give the smoke widths several tiles per slot."""
        from repro.kernels import decode_attn
        monkeypatch.setattr(decode_attn, "TILE_BYTES", 2 * 32 * 4 * 16)
        cfg, model, params = danube
        eng = DecodeEngine(model, params, 2, 128, block_size=4)
        _admit_all(eng, cfg, model, params, [16, 40], max_new=20)
        ((S, *_), layers), = eng._attn_calls
        assert (S, layers) == (64, 2)
        eng.step_block()
        keys = [min(L + i, S) for i in range(1, 5) for L in (16, 40)]
        assert eng.kv_tiles_fetched == layers * sum(-(-k // 16) for k in keys)
        assert eng.kv_tiles_capacity == layers * len(keys) * S // 16
        fetched = eng.kv_tiles_fetched
        eng.step_block()
        assert eng.kv_tiles_fetched > fetched

    def test_block_compiles_once(self, danube):
        cfg, model, params = danube
        eng = DecodeEngine(model, params, 4, 128, block_size=4)
        _admit_all(eng, cfg, model, params, [16, 20, 24, 30], max_new=13)
        eng.run_until_drained()            # several blocks, ragged finish
        assert eng.block_compiles == 1
        # admit again (different lengths): still one compiled block program
        _admit_all(eng, cfg, model, params, [40, 8, 12, 50], max_new=5,
                   seed=9)
        eng.run_until_drained()
        assert eng.block_compiles == 1

    def test_truncation_flag_and_counter(self, danube):
        cfg, model, params = danube
        eng = DecodeEngine(model, params, 2, 64, block_size=4)
        # rid 0 hits the capacity wall with budget left; rid 1 finishes clean
        entries, n = _admit_all(eng, cfg, model, params, [60, 16],
                                max_new=30)
        assert n == 2
        eng.run_until_drained()
        trunc, clean = eng.outputs[0], eng.outputs[1]
        assert trunc.finished and trunc.truncated
        # first token + the 3 decode steps that fit before capacity-1
        assert len(trunc.output_tokens) == 4
        assert clean.finished and not clean.truncated
        assert len(clean.output_tokens) == 31          # first + 30
        assert eng.truncations == 1

    def test_capacity_wall_admission_boundary(self, danube):
        """A slot admitted AT the capacity wall (prompt_len == capacity-1)
        must behave identically in both loops: emit exactly one token, then
        retire truncated."""
        cfg, model, params = danube
        block = DecodeEngine(model, params, 1, 64, block_size=4)
        per_tok = DecodeEngine(model, params, 1, 64, block_size=4)
        _admit_all(block, cfg, model, params, [63], max_new=10)
        _admit_all(per_tok, cfg, model, params, [63], max_new=10)
        block.run_until_drained()
        while per_tok.active.any():
            per_tok.step()
        assert (block.outputs[0].output_tokens
                == per_tok.outputs[0].output_tokens)
        assert len(block.outputs[0].output_tokens) == 2  # first + 1 decode
        assert block.outputs[0].truncated and per_tok.outputs[0].truncated
        assert block.budget[0] == per_tok.budget[0]
        assert block.lengths[0] == per_tok.lengths[0]

    def test_per_token_truncation_matches(self, danube):
        """The satellite fix: the legacy step() loop must also report the
        capacity-wall retirement as truncated."""
        cfg, model, params = danube
        eng = DecodeEngine(model, params, 1, 64, block_size=4)
        _admit_all(eng, cfg, model, params, [60], max_new=50)
        while eng.active.any():
            eng.step()
        assert eng.outputs[0].truncated and eng.truncations == 1


class TestAdmission:
    def test_batched_matches_serial(self, kimi):
        cfg, model, params = kimi
        lens = [16, 22, 30]
        batched = DecodeEngine(model, params, 4, 128, block_size=4)
        serial = DecodeEngine(model, params, 4, 128, block_size=4)
        entries, n = _admit_all(batched, cfg, model, params, lens, max_new=4)
        assert n == 3
        for e in entries:
            assert serial.admit(*e)
        batched.run_until_drained()
        serial.run_until_drained()
        for i in range(3):
            assert (batched.outputs[i].output_tokens
                    == serial.outputs[i].output_tokens)

    def test_admits_up_to_free_slots(self, danube):
        cfg, model, params = danube
        eng = DecodeEngine(model, params, 2, 128, block_size=4)
        entries, n = _admit_all(eng, cfg, model, params, [16, 20, 24],
                                max_new=3)
        assert n == 2 and not eng.free_slots()
        eng.run_until_drained()
        assert len(eng.free_slots()) == 2
        assert eng.admit_many(entries[2:]) == 1

    def test_deployment_overflow_drains_and_admits_rest(self, danube):
        """A batch larger than a region's decode slots must not silently
        drop requests: the deployment drains active streams and admits the
        remainder, and every request gets a finished Response."""
        from repro.serving import CrossDCDeployment, DeploymentConfig
        cfg, model, params = danube
        dep = CrossDCDeployment(model, params,
                                DeploymentConfig(threshold=1024,
                                                 decode_slots=2,
                                                 capacity=128))
        rng = np.random.default_rng(5)
        reqs = [Request(rid=i, tokens=rng.integers(
            0, cfg.vocab_size, (L,)).astype(np.int32), max_new_tokens=3)
            for i, L in enumerate([16, 20, 24, 30, 40])]
        out = dep.submit_batch(reqs)
        assert sorted(out) == [0, 1, 2, 3, 4]
        assert all(r.finished for r in out.values())
        assert all(len(r.output_tokens) == 4 for r in out.values())

    def test_free_slot_deque_recycling(self, danube):
        cfg, model, params = danube
        eng = DecodeEngine(model, params, 3, 128, block_size=4)
        assert eng.free_slots() == [0, 1, 2]
        entries, _ = _admit_all(eng, cfg, model, params, [16, 20], max_new=2)
        assert eng.free_slots() == [2]
        eng.run_until_drained()
        # retired slots return to the tail; next admit pops from the head
        assert set(eng.free_slots()) == {0, 1, 2}
        assert eng.free_slots()[0] == 2
        eng.admit_many(entries[:1])
        assert eng.active[2] and not eng.active[0]
