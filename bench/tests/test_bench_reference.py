"""The plain reference computes what the program's model computes: at toy
widths in float32 on the CPU, its next-token logits at every position
equal the program's own (kernels off, highest precision) to rounding."""
import copy
from pathlib import Path

import numpy as np
import pytest

from bench import reference
from bench.model import model_config
from bench.spec import load_json
from bench.weights import make_params

DATA = Path(__file__).resolve().parent / "data"


@pytest.mark.parametrize("name", ["tiny-hybrid", "tiny-dense"])
def test_reference_matches_the_program_in_float32(name):
    import jax
    import jax.numpy as jnp

    from repro.models import Model

    config = copy.deepcopy(load_json(DATA / f"{name}.json"))
    config["model"]["dtype"] = "float32"
    params = make_params(config, 7)
    model = Model(model_config(config), use_kernels=False)
    B, T = 2, 128
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                              config["model"]["vocab_size"])
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    with jax.default_matmul_precision("highest"):
        hidden, _ = model.prefill_chunk(params, {
            "tokens": toks, "positions": pos,
            "lengths": jnp.full((B,), T, jnp.int32)})
        want = np.asarray(jax.vmap(lambda h: model._logits(params, h))(
            hidden))
    got = np.asarray(reference.logits(config["model"], params, toks, pos))
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_control_rounds_to_float8():
    import jax.numpy as jnp

    x = jnp.asarray([1.0, 1.06, 448.0, -3.3], jnp.float32)
    y = np.asarray(reference._fp8(x))
    # e4m3 keeps 3 mantissa bits: 1.06 rounds to 1.0, 3.3 to 3.25
    assert y[0] == 1.0 and y[1] == 1.0 and y[2] == 448.0
    assert y[3] == pytest.approx(-3.25)
