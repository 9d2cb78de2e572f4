"""The plain reference: the configuration's forward pass in float32 jnp.

It imports nothing of the program.  It reads the configuration file's
``model`` section and the weights ``bench/weights.py`` made (upcast to
float32 as they are used), and runs every matmul at
``Precision.HIGHEST``.  The model is one pre-norm residual stack: the
embedding, then per block RMSNorm and the mixer, RMSNorm and the FFN (an
FFN of kind ``none`` is left out), then the final RMSNorm and the output
head.  Each mixer's and FFN's equations are in the file of its kind,
``bench/layers/<part>.<kind>.py`` (``bench/layers/__init__.py``); a kind
with no file raises ``LookupError``.

``low=True`` is the control: the same equations with every matmul operand
rounded to float8 e4m3 under a per-tensor scale, the precision below the
configuration's bfloat16 that would tempt a later change.

Sequences are teacher-forced: ``tokens`` (B, T) holds each prompt followed by
the tokens the program served; ``read`` (B, P) the positions whose next-token
logits are wanted.  Padding past a sequence's end cannot reach earlier
positions (everything is causal).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import layers
from bench.layers import _fp8, _lin, _rms  # noqa: F401 (_fp8: the control)


@functools.partial(jax.jit, static_argnames=("block", "eps", "low"))
def _block(x, p, r, *, block, eps, low):
    """One residual block; ``p`` is the group's stacked (or shared) params,
    ``r`` the repeat to take (ignored for a shared block)."""
    block = _thaw(block)
    if not block.get("shared", False):
        p = jax.tree.map(lambda a: a[r], p)
    mixer, ffn = block["mixer"], block["ffn"]
    x = x + layers.module("mixer", mixer["kind"]).forward(
        p["mixer"], _rms(x, p["ln1"], eps), mixer, eps=eps, low=low)
    if ffn["kind"] != "none":
        x = x + layers.module("ffn", ffn["kind"]).forward(
            p["ffn"], _rms(x, p["ln2"], eps), ffn, eps=eps, low=low)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _logits(x, read, final_norm, unembed, *, eps, low):
    h = jnp.take_along_axis(x, read[..., None], axis=1)      # (B, P, d)
    return _lin(_rms(h, final_norm, eps), unembed, low)      # (B, P, V)


def _freeze(obj):
    if isinstance(obj, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in obj.items()))
    if isinstance(obj, list):
        return ("__list__",) + tuple(_freeze(v) for v in obj)
    return obj


def _thaw(obj):
    if isinstance(obj, tuple) and obj and obj[0] == "__list__":
        return [_thaw(v) for v in obj[1:]]
    if isinstance(obj, tuple) and all(isinstance(e, tuple) and len(e) == 2
                                      for e in obj):
        return {k: _thaw(v) for k, v in obj}
    return obj


def logits(model: dict, params, tokens, read, *, low: bool = False):
    """Next-token logits (B, P, V) float32 at positions ``read`` (B, P) of
    the teacher-forced ``tokens`` (B, T); T a multiple of 64."""
    eps = float(model["norm_eps"])
    x = params["embed"][tokens].astype(jnp.float32)
    for gi, g in enumerate(model["groups"]):
        gp = params["groups"][gi]
        for r in range(int(g["repeats"])):
            for bi, name in enumerate(g["blocks"]):
                block = model["blocks"][name]
                p = (gp["shared"] if block.get("shared") else
                     gp["stacked"])[f"b{bi}"]
                x = _block(x, p, jnp.int32(r), block=_freeze(block),
                           eps=eps, low=low)
    return _logits(x, read, params["final_norm"], params["unembed"],
                   eps=eps, low=low)
