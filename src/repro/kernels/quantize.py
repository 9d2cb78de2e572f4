"""Fused int8 quantize-on-write for the KV wire format (Pallas).

``distributed.collectives.quantize_int8`` is a per-tensor symmetric int8
encode: absmax reduction, scale = max(absmax, 1e-30)/127, round/clip. As a
plain jnp chain on the admission path it is a separate multi-op pass over
every wire-eligible cache leaf (abs, global max, divide, round, clip, cast
— each materializing an intermediate). This kernel fuses the whole encode
into one tiled pass: a two-phase sequential grid first reduces the absmax
into VMEM scratch, then encodes each tile against the shared scale, so the
leaf is read twice and written once (int8) with no fp32 intermediates in
HBM.

The math is kept operation-for-operation identical to ``quantize_int8``
(same max/round/clip primitives in the same order), so the produced wire
pytree is byte-identical to the unfused path — pinned by
``tests/test_kernels_quantize.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows per grid step of the flattened (rows, LANE) view of the leaf
_TILE_ROWS = 256
_LANE = 128


def _quantize_kernel(x_ref, q_ref, scale_ref, amax, *, num_tiles):
    """Grid (2, num_tiles): phase 0 reduces |x| into ``amax`` scratch,
    phase 1 encodes every tile against the finished per-tensor scale.

    The absmax and scale stay (1, 1) vectors end to end: the TPU cannot
    store a scalar to VMEM."""
    phase = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((phase == 0) & (j == 0))
    def _zero():
        amax[...] = jnp.zeros_like(amax)

    x = x_ref[...].astype(jnp.float32)

    @pl.when(phase == 0)
    def _reduce():
        tile = jnp.max(jnp.max(jnp.abs(x), axis=0, keepdims=True), axis=1,
                       keepdims=True)                   # (1, 1)
        amax[...] = jnp.maximum(amax[...], tile)

    @pl.when(phase == 1)
    def _encode():
        # multiply by the f32 reciprocal instead of dividing: XLA rewrites
        # constant divisions to reciprocal multiplies under jit, so an
        # explicit multiply is the only form that is bit-stable between
        # this (jitted) kernel and the eager jnp ref
        scale = jnp.maximum(amax[...], 1e-30) * (1.0 / 127.0)
        q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127
                              ).astype(jnp.int8)

        @pl.when(j == 0)
        def _emit_scale():
            scale_ref[...] = scale


def quantize_int8_fused(x, *, interpret: bool = False):
    """Per-tensor symmetric int8 quantization as one fused Pallas pass.

    Returns (q: int8, scale: float32 scalar) — byte-identical to
    ``distributed.collectives.quantize_int8(x)``.
    """
    shape = x.shape
    n = x.size
    rows = -(-n // _LANE)
    tiles = -(-rows // _TILE_ROWS)
    pad = tiles * _TILE_ROWS * _LANE - n
    flat = jnp.pad(x.reshape(-1), (0, pad))  # zeros never win the absmax
    xr = flat.reshape(tiles * _TILE_ROWS, _LANE)

    kernel = functools.partial(_quantize_kernel, num_tiles=tiles)
    q, scale = pl.pallas_call(
        kernel,
        name="quantize",
        grid=(2, tiles),
        in_specs=[pl.BlockSpec((_TILE_ROWS, _LANE), lambda p, j: (j, 0))],
        out_specs=[
            # phase 0 parks on block 0 and writes nothing, so no garbage
            # tile is written back before phase 1 encodes it
            pl.BlockSpec((_TILE_ROWS, _LANE), lambda p, j: (j * p, 0)),
            pl.BlockSpec((1, 1), lambda p, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xr.shape, jnp.int8),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32)],
        interpret=interpret,
    )(xr)
    q = q.reshape(-1)[:n].reshape(shape)
    return q, scale[0, 0]
