"""One file per layer kind: the plain reference's equations and the model
FLOPs of each mixer and FFN kind a configuration uses.

``bench/layers/<part>.<kind>.py``, where ``part`` is ``mixer`` or ``ffn`` and
``kind`` the spec's ``kind``, defines

    forward(p, x, spec, *, eps, low)  the residual branch's output, float32;
                                      every matmul goes through ``_mm`` or
                                      ``_lin``, so the float8 control covers it
    matmul_params(spec, d_model)      the matmul weights a token passes
                                      through (for experts: those it uses)
    state_flops(spec)                 optional, default 0: FLOPs per token
                                      that do not depend on the context
    pair_flops(spec, phase)           optional, default 0: FLOPs per causal
                                      query-key pair; ``phase`` is
                                      ``"prefill"`` or ``"decode"``

so a configuration brings a new kind by adding a file.  The helpers below
are shared by the files; like them, they import nothing of the program.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp

DIR = Path(__file__).resolve().parent
HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def module(part: str, kind: str):
    """The module of ``bench/layers/<part>.<kind>.py``."""
    return _load(DIR / f"{part}.{kind}.py")


@functools.lru_cache(maxsize=None)
def _load(path: Path):
    if not path.is_file():
        raise LookupError(f"no layer file {path}: add it to bring this kind")
    spec = importlib.util.spec_from_file_location(
        "bench_layer_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fp8(x):
    s = jnp.max(jnp.abs(x)) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, low):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _lin(x, w, low):
    return _mm("...i,io->...o", x, w, low)


def _rope(x, theta):
    """x: (B, H, T, D); rotates pairs (2i, 2i+1) by position * theta^(-2i/D)."""
    B, H, T, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     -1).reshape(B, H, T, D)
