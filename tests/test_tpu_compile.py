"""Every Pallas kernel of ``repro.kernels`` compiles for a TPU v5e chip.

No chip is needed: the TPU compiler is installed and compiles for a chip
that is described, not attached, so these tests catch what interpret mode
cannot (block shapes off the (8, 128) tiling, scalar stores to VMEM, too
much fast memory).  Widths are the published ones of the models the
served path targets: zamba2-1.2b (shared MHA attention, Mamba2),
kimi-linear-1t (MLA attention, KDA delta rule) and mistral-nemo-12b (GQA,
the benchmark's model).  Each test checks that the
compiled program contains the kernel (``tpu_custom_call``), named in the
HLO text by its ``pallas_call`` name.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and a test file is imported by
every test worker.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import AttentionSpec, LinearSpec
from repro.kernels.decode_attn import decode_attention
from repro.kernels.delta import delta_chunked_fused
from repro.kernels.flash_attn import flash_attention
from repro.kernels.gla import gla_chunked_fused
from repro.kernels.paged_decode_attn import paged_decode_attention
from repro.kernels.paged_prefill_attn import paged_prefill_attention
from repro.kernels.quantize import quantize_int8_fused

SEQ = 1024            # prefill bucket / chunk
CAPACITY = 4096       # decode KV capacity per slot
SLOTS = 8
PAGE = 16             # page tokens (the deployment's block size)


def _mixers(arch, kind):
    return [b.mixer for g in get_config(arch).groups for b in g.blocks
            if isinstance(b.mixer, kind)]


MISTRAL_ATTN = _mixers("mistral-nemo-12b", AttentionSpec)[0]
ZAMBA_ATTN = _mixers("zamba2-1.2b", AttentionSpec)[0]
ZAMBA_MAMBA = _mixers("zamba2-1.2b", LinearSpec)[0]
KIMI_MLA = _mixers("kimi-linear-1t", AttentionSpec)[0]
KIMI_KDA = _mixers("kimi-linear-1t", LinearSpec)[0]


def _attention_widths(spec):
    """(Hq, Hkv, Dk, Dv) of the kernel call: GQA runs per head; MLA prefill
    attends with rope-augmented keys, MLA decode over the latent cache."""
    if spec.kind == "mla":
        return {"prefill": (spec.q_heads, spec.kv_heads,
                            spec.head_dim + spec.mla_rope_dim, spec.head_dim),
                "decode": (spec.q_heads, 1,
                           spec.mla_kv_rank + spec.mla_rope_dim,
                           spec.mla_kv_rank)}
    w = (spec.q_heads, spec.kv_heads, spec.head_dim, spec.head_dim)
    return {"prefill": w, "decode": w}


ATTN = {"zamba2": _attention_widths(ZAMBA_ATTN),
        "kimi-mla": _attention_widths(KIMI_MLA),
        "mistral-nemo": _attention_widths(MISTRAL_ATTN)}


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip, with JAX's persistent compilation cache off
    (a compile for a described chip is written to it but cannot be read
    back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compile(kernel, fn, sharding, *shapes):
    """Compile ``fn`` and check that its program holds the Pallas call,
    named by ``kernel`` (the ``pallas_call`` name) in the HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert re.search(rf"%{kernel}(\.\d+)? = .*custom_call_target="
                     r'"tpu_custom_call"', compiled.as_text()), kernel
    return compiled


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.mark.parametrize("model", sorted(ATTN))
def test_flash_attention_compiles(one_chip, model):
    H, Hkv, Dk, Dv = ATTN[model]["prefill"]
    _compile("flash_attn", flash_attention, one_chip,
             ((1, H, SEQ, Dk), BF16), ((1, Hkv, SEQ, Dk), BF16),
             ((1, Hkv, SEQ, Dv), BF16))


def test_flash_attention_chunk_compiles(one_chip):
    """A chunked-prefill chunk: queries attend over prior + own keys."""
    H, Hkv, Dk, Dv = ATTN["zamba2"]["prefill"]
    _compile("flash_attn", functools.partial(flash_attention, q_offset=SEQ),
             one_chip, ((1, H, SEQ, Dk), BF16), ((1, Hkv, 2 * SEQ, Dk), BF16),
             ((1, Hkv, 2 * SEQ, Dv), BF16))


@pytest.mark.parametrize("model", sorted(ATTN))
def test_decode_attention_compiles(one_chip, model):
    H, Hkv, Dk, Dv = ATTN[model]["decode"]
    _compile("decode_attn", decode_attention, one_chip,
             ((SLOTS, H, Dk), BF16), ((SLOTS, Hkv, CAPACITY, Dk), BF16),
             ((SLOTS, Hkv, CAPACITY, Dv), BF16), ((SLOTS,), I32))


def test_decode_attention_signature_is_the_benchmarks(one_chip):
    """At the benchmark's decode widths (mistral-nemo-12b: 32 q / 8 kv
    heads of 128, 4 slots of 4,096 keys) the ``decode_attn`` call keeps
    the operand signature by which the benchmark's trace reduction tells
    it apart: ``bench/kernels/decode_attn.py`` accepts it and
    ``bench/kernels/flash_attn.py`` does not."""
    from jax._src.lib import _jax

    from bench import trace
    from bench.kernels import decode_attn as bench_decode
    from bench.kernels import flash_attn as bench_flash

    H, Hkv, Dk, Dv = ATTN["mistral-nemo"]["decode"]
    slots = 4
    compiled = _compile("decode_attn", decode_attention, one_chip,
                        ((slots, H, Dk), BF16),
                        ((slots, Hkv, CAPACITY, Dk), BF16),
                        ((slots, Hkv, CAPACITY, Dv), BF16), ((slots,), I32))
    opts = _jax.HloPrintOptions.short_parsable()
    opts.print_operand_shape = True          # as the profiler names ops
    opts.print_percent = True
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    sigs = [trace.signature(line.strip()) for line in text.splitlines()
            if line.strip().startswith("%decode_attn")]
    assert sigs == [f"bf16[{slots * H},1,{Dv}] <- s32[{slots}] "
                    f"bf16[{slots * H},1,{Dk}] "
                    f"bf16[{slots * Hkv},{CAPACITY},{Dk}] "
                    f"bf16[{slots * Hkv},{CAPACITY},{Dv}]"]
    assert bench_decode.match(sigs[0])
    assert not bench_flash.match(sigs[0])


@pytest.mark.parametrize("model", sorted(ATTN))
def test_paged_decode_attention_compiles(one_chip, model):
    H, Hkv, Dk, Dv = ATTN[model]["decode"]
    pool = SLOTS * CAPACITY // PAGE + 1
    _compile("paged_decode_attn", paged_decode_attention, one_chip,
             ((SLOTS, H, Dk), BF16),
             ((Hkv, pool, PAGE, Dk), BF16), ((Hkv, pool, PAGE, Dv), BF16),
             ((SLOTS, CAPACITY // PAGE), I32), ((SLOTS,), I32))


def test_paged_prefill_attention_compiles(one_chip):
    """A 128-token suffix chunk over a 1,024-token cached prefix."""
    H, Hkv, D, _ = ATTN["zamba2"]["prefill"]
    pool = SLOTS * CAPACITY // PAGE + 1
    C = 128
    _compile("paged_prefill_attn", paged_prefill_attention, one_chip,
             ((1, H, C, D), BF16),
             ((Hkv, pool, PAGE, D), BF16), ((Hkv, pool, PAGE, D), BF16),
             ((1, SEQ // PAGE), I32),
             ((1, Hkv, C, D), BF16), ((1, Hkv, C, D), BF16))


def test_gla_fused_compiles(one_chip):
    """zamba2's Mamba2 mixer: the length-masked chunked GLA kernel."""
    B, m = 2, ZAMBA_MAMBA
    _compile("gla", gla_chunked_fused, one_chip,
             ((B, m.heads, SEQ, m.key_dim), BF16),
             ((B, m.heads, SEQ, m.key_dim), BF16),
             ((B, m.heads, SEQ, m.value_dim), BF16),
             ((B, m.heads, SEQ), F32), ((B,), I32),
             ((B, m.heads, m.key_dim, m.value_dim), F32))


def test_delta_fused_compiles(one_chip):
    """kimi-linear-1t's KDA mixer: the length-masked chunked delta rule."""
    B, m = 1, KIMI_KDA
    _compile("delta", delta_chunked_fused, one_chip,
             ((B, m.heads, SEQ, m.key_dim), BF16),
             ((B, m.heads, SEQ, m.key_dim), BF16),
             ((B, m.heads, SEQ, m.value_dim), BF16),
             ((B, m.heads, SEQ), F32), ((B, m.heads, SEQ), F32),
             ((B,), I32), ((B, m.heads, m.key_dim, m.value_dim), F32))


def test_quantize_compiles(one_chip):
    """The int8 wire encode of one K leaf of a 3.5K-token zamba2 prompt
    (6 shared-attention invocations), upcast to f32 as the wire path does."""
    _, Hkv, D, _ = ATTN["zamba2"]["prefill"]
    _compile("quantize", quantize_int8_fused, one_chip,
             ((6, 1, 3500, Hkv, D), F32))
