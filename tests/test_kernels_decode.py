"""Flash-decode Pallas kernel vs oracle: lengths, windows, GQA, dtypes,
the live-tile clamp, and the host count of the tiles it copies."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attn import (_live_tiles, decode_attention,
                                       pick_block_k, tiles_fetched)

pytestmark = pytest.mark.slow      # JAX compiles dominate; -m "not slow" skips

RNG = np.random.default_rng(3)


def mk(*shape):
    return jnp.asarray(RNG.standard_normal(shape).astype(np.float32))


@pytest.mark.parametrize("B,Hq,Hkv,S,D,lens,block_k", [
    pytest.param(2, 8, 8, 256, 64, None, 64, id="2-8-8-256-64"),
    # GQA + ragged
    pytest.param(3, 8, 4, 300, 64, None, 64, id="3-8-4-300-64"),
    # MQA
    pytest.param(1, 16, 1, 512, 128, None, 64, id="1-16-1-512-128"),
    # group 4 at D 128 (the kernel's own 256-key f32 tiles): most of the
    # 8 tiles of each slot are dead
    pytest.param(4, 32, 8, 2048, 128, [1, 100, 513, 1030], None,
                 id="group4-mostly-dead"),
    # one key and the full capacity in the same batch
    pytest.param(2, 8, 2, 512, 64, [1, 512], 64, id="len1-and-full"),
    # 600 keys are not a multiple of the kernel's 256-key tile
    pytest.param(3, 16, 8, 600, 128, [600, 257, 255], None,
                 id="S-not-multiple-of-tile"),
    # MQA with all 64 query heads over one KV head
    pytest.param(2, 64, 1, 1024, 128, [1024, 333], 256, id="mqa-group64"),
])
def test_decode_matches_oracle(B, Hq, Hkv, S, D, lens, block_k):
    q = mk(B, Hq, D)
    kc, vc = mk(B, Hkv, S, D), mk(B, Hkv, S, D)
    if lens is None:
        lens = RNG.integers(1, S + 1, B)
    lens = jnp.asarray(lens, jnp.int32)
    out = decode_attention(q, kc, vc, lens, interpret=True, block_k=block_k)
    want = ref.decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window,B,Hq,Hkv,S,D,lens,block_k", [
    pytest.param(16, 2, 4, 2, 256, 32, [50, 256], 64, id="16"),
    pytest.param(64, 2, 4, 2, 256, 32, [50, 256], 64, id="64"),
    pytest.param(200, 2, 4, 2, 256, 32, [50, 256], 64, id="200"),
    # the lower clamp skips the first 6 of 8 tiles of the long slot, and a
    # window wider than a short slot keeps all of its keys
    pytest.param(300, 3, 32, 8, 2048, 128, [2048, 700, 1], None,
                 id="300-skips-leading-tiles"),
])
def test_decode_window(window, B, Hq, Hkv, S, D, lens, block_k):
    q, kc, vc = mk(B, Hq, D), mk(B, Hkv, S, D), mk(B, Hkv, S, D)
    lens = jnp.asarray(lens, jnp.int32)
    out = decode_attention(q, kc, vc, lens, window=window, interpret=True,
                           block_k=block_k)
    want = ref.decode_attention_ref(q, kc, vc, lens, window=window)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_decode_tiny_lengths():
    """length=1 attends a single key."""
    B, H, S, D = 2, 2, 128, 32
    q, kc, vc = mk(B, H, D), mk(B, H, S, D), mk(B, H, S, D)
    lens = jnp.asarray([1, 1], jnp.int32)
    out = decode_attention(q, kc, vc, lens, interpret=True, block_k=64)
    np.testing.assert_allclose(out, vc[:, :, 0], atol=2e-5, rtol=2e-5)


def test_decode_dk_neq_dv():
    """Absorbed-MLA shape: K latent+rope, V latent."""
    B, Hq, S = 2, 6, 192
    q, kc, vc = mk(B, Hq, 80), mk(B, 1, S, 80), mk(B, 1, S, 64)
    lens = jnp.asarray([100, 192], jnp.int32)
    out = decode_attention(q, kc, vc, lens, interpret=True, block_k=64)
    want = ref.decode_attention_ref(q, kc, vc, lens)
    assert out.shape == (B, Hq, 64)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


def test_decode_bf16():
    B, H, S, D = 1, 4, 128, 64
    q = mk(B, H, D).astype(jnp.bfloat16)
    kc = mk(B, H, S, D).astype(jnp.bfloat16)
    vc = mk(B, H, S, D).astype(jnp.bfloat16)
    lens = jnp.asarray([100], jnp.int32)
    out = decode_attention(q, kc, vc, lens, interpret=True, block_k=64)
    want = ref.decode_attention_ref(q, kc, vc, lens)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32), atol=3e-2, rtol=3e-2)


def test_decode_bf16_gqa():
    """bf16 caches at group 4 and D 128: bf16 scores, f32 softmax."""
    B, Hq, Hkv, S, D = 2, 16, 4, 1024, 128
    q = mk(B, Hq, D).astype(jnp.bfloat16)
    kc = mk(B, Hkv, S, D).astype(jnp.bfloat16)
    vc = mk(B, Hkv, S, D).astype(jnp.bfloat16)
    lens = jnp.asarray([1024, 77], jnp.int32)
    out = decode_attention(q, kc, vc, lens, interpret=True, block_k=256)
    want = ref.decode_attention_ref(q, kc, vc, lens)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(np.float32),
                               want.astype(np.float32), atol=3e-2, rtol=3e-2)


def test_block_k_of_the_served_widths():
    """The tile the kernel picks: mistral-nemo's 8 KV heads of 128 at 4096
    keys in bf16 give 512 keys, 8 tiles per slot (32 grid steps for 4
    slots); a capacity under one tile is one tile."""
    assert pick_block_k(4096, 8, 128, 128, 2) == 512
    assert pick_block_k(4096, 32, 64, 64, 2) == 256        # zamba2 MHA
    assert pick_block_k(4096, 1, 536, 472, 2) == 1024      # kimi MLA latent
    assert pick_block_k(300, 8, 128, 128, 2) == 300
    for args in [(4096, 8, 128, 128, 2), (4096, 1, 536, 472, 2),
                 (1 << 16, 64, 256, 256, 4)]:
        bk = pick_block_k(*args)
        assert bk & (bk - 1) == 0 and bk >= 16
        assert bk == 16 or args[1] * (args[2] + args[3]) * args[4] * bk \
            <= 2 << 20


@pytest.mark.parametrize("seed", range(4))
def test_tiles_fetched_matches_brute_force(seed):
    """Tiles copied = per slot, the tiles that hold a live key (at least
    one: a slot with no key still holds its first tile); and the kernel's
    clamped index map, walked over the grid, changes block exactly that
    often."""
    rng = np.random.default_rng(seed)
    S, kv_heads, dk, dv, itemsize = [(4096, 8, 128, 128, 2),
                                     (1000, 8, 128, 128, 4),
                                     (4096, 1, 536, 472, 2),
                                     (64, 2, 32, 32, 4)][seed]
    bk = pick_block_k(S, kv_heads, dk, dv, itemsize)
    nk = -(-S // bk)
    for window in [0, 1, 37, bk, 3 * bk + 5, 2 * S]:
        lens = rng.integers(0, S + 1, 16)
        lens[:3] = [0, 1, S]
        want = 0
        for L in lens:
            live = {p // bk for p in range(max(0, L - window) if window
                                           else 0, L)}
            want += max(1, len(live))
            lo, hi = _live_tiles(jnp.int32(L), bk, window)
            idx = [min(max(j, int(lo)), int(hi)) for j in range(nk)]
            walked = 1 + sum(a != b for a, b in zip(idx, idx[1:]))
            assert walked == max(1, len(live)), (L, window)
        got, cap = tiles_fetched(lens, S, window, kv_heads=kv_heads, dk=dk,
                                 dv=dv, itemsize=itemsize)
        assert (got, cap) == (want, len(lens) * nk), window
