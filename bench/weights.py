"""Random weights from the seed, made on the device in one jitted call.

The program decides the layout of its parameter tree (``Model.init`` under
``jax.eval_shape``: nothing is computed); the benchmark fills every leaf
itself, by the leaf's name, so the plain reference (``reference.py``) runs
on weights the program did not make.  The distributions follow the usual
initialisations of the two families:

* linear weights ``w``: N(0, 1/d_in); the residual branches' output
  projections (``wo``, ``w2``) are scaled down by ``1/sqrt(2 * layers)``
  (GPT-2), so the residual stream stays O(1) and the model is not chaotic
  in its rounding;
* ``embed``: N(0, 1); ``unembed``: N(0, (LOGIT_STD^2) / d), so logits have
  a spread of about LOGIT_STD, like a trained model's;
* RMSNorm scales (``ln1``, ``ln2``, ``final_norm``, ``g_norm``): 1 + N(0, 0.1^2);
* Mamba2: ``A_log`` = log U(1, 16), ``dt_bias`` = softplus^-1 of a
  log-uniform step in [1e-3, 1e-1], ``D_skip`` = 1, ``conv_w``: N(0, 1/K);
* any other leaf: biases zero, matrices N(0, 1/d_in).
"""
from __future__ import annotations

import functools
import json
import math

LOGIT_STD = 2.0
NORM_NOISE = 0.1


def n_layers(config: dict) -> int:
    return sum(len(g["blocks"]) * int(g["repeats"])
               for g in config["model"]["groups"])


def param_layout(config: dict):
    """The program's parameter tree as ShapeDtypeStructs."""
    import jax

    from bench.model import model_config
    from repro.models import Model

    model = Model(model_config(config))
    return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))


def _leaf(key, path, shape, dtype, layers, d_model):
    import jax
    import jax.numpy as jnp

    names = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
    name = names[-1]
    parent = names[-2] if len(names) > 1 else None
    f32 = jnp.float32

    def normal(std):
        return (jax.random.normal(key, shape, dtype) * std).astype(dtype)

    if name == "embed":
        return normal(1.0)
    if name == "unembed":
        return normal(LOGIT_STD / math.sqrt(d_model))
    if name in ("ln1", "ln2", "ln_cross", "final_norm", "g_norm",
                "q_norm", "kv_norm", "enc_norm"):
        return (1.0 + NORM_NOISE * jax.random.normal(key, shape, f32)
                ).astype(dtype)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0)
                       ).astype(dtype)
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name == "D_skip":
        return jnp.ones(shape, dtype)
    if name == "conv_w":
        return normal(shape[-2] ** -0.5)
    if name == "b" or len(shape) < 2:
        return jnp.zeros(shape, dtype)
    std = shape[-2] ** -0.5
    if (name == "w" and parent == "wo") or name == "w2":
        std /= math.sqrt(2 * layers)      # residual-branch output projection
    return normal(std)


@functools.lru_cache(maxsize=4)
def _generator(config_json: str):
    import jax

    config = json.loads(config_json)
    flat, tree = jax.tree_util.tree_flatten_with_path(param_layout(config))
    layers = n_layers(config)
    d_model = int(config["model"]["d_model"])

    @jax.jit
    def gen(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        return [_leaf(jax.random.fold_in(key, i), path, s.shape, s.dtype,
                      layers, d_model)
                for i, (path, s) in enumerate(flat)]

    return gen, tree


def make_params(config: dict, seed: int):
    """Every weight of ``config``'s model from ``seed``, in the dtype it is
    served in, in one jitted call on the default device."""
    import jax
    import jax.numpy as jnp

    gen, tree = _generator(json.dumps(config,
                                      sort_keys=True))
    seed = int(seed)
    leaves = gen(jnp.uint32(seed & 0xFFFFFFFF),
                 jnp.uint32((seed >> 32) & 0xFFFFFFFF))
    return jax.block_until_ready(jax.tree_util.tree_unflatten(tree, leaves))
