"""Layer kinds are found by file name: the plain reference and the model
FLOP count take each mixer and FFN kind from ``bench/layers/<part>.<kind>.py``,
refuse a kind with no file, and use a kind brought by a new file alone."""
import copy
import inspect
from pathlib import Path

import numpy as np
import pytest

from bench import flops, layers, reference, shapes
from bench.spec import load_json

DATA = Path(__file__).resolve().parent / "data"
LAYER_FILES = sorted(p.stem for p in layers.DIR.glob("*.*.py"))

TOY = '''
from bench.layers import _lin


def forward(p, x, spec, *, eps, low):
    return spec["scale"] * _lin(x, p["w"], low)


def matmul_params(spec, d_model):
    return d_model * d_model


def state_flops(spec):
    return 7.0


def pair_flops(spec, phase):
    return {"prefill": 3.0, "decode": 5.0}[phase]
'''


def _toy_config(ffn_kind):
    return {"name": "toy", "model": {
        "d_model": 8, "vocab_size": 16, "norm_eps": 1e-5, "dtype": "float32",
        "blocks": {"b": {"mixer": {"type": "linear", "kind": "toy",
                                   "scale": 0.5},
                         "ffn": {"kind": ffn_kind, "scale": 0.25}}},
        "groups": [{"blocks": ["b"], "repeats": 2}]}}


def _toy_params(d, V, reps, seed=3):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    block = {"ln1": 1 + 0.1 * normal(reps, d),
             "ln2": 1 + 0.1 * normal(reps, d),
             "mixer": {"w": normal(reps, d, d) / np.sqrt(d)},
             "ffn": {"w": normal(reps, d, d) / np.sqrt(d)}}
    return {"embed": normal(V, d), "final_norm": 1 + 0.1 * normal(d),
            "unembed": normal(d, V) / np.sqrt(d),
            "groups": [{"stacked": {"b0": block}}]}


def _rms(x, w, eps=1e-5):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


@pytest.fixture
def toy_dir(tmp_path, monkeypatch):
    """``mixer.toy.py`` and ``ffn.toy.py`` in a directory of their own."""
    for part in ("mixer", "ffn"):
        (tmp_path / f"{part}.toy.py").write_text(TOY)
    monkeypatch.setattr(layers, "DIR", tmp_path)
    return tmp_path


def _with_kind(part, kind):
    config = copy.deepcopy(load_json(DATA / "tiny-dense.json"))
    config["model"]["blocks"]["attn"][part]["kind"] = kind
    return config


@pytest.mark.parametrize("use", ["reference", "flops"])
@pytest.mark.parametrize("part", ["mixer", "ffn"])
def test_a_kind_with_no_file_raises_naming_the_file(part, use):
    import jax.numpy as jnp

    from bench.weights import make_params

    config = _with_kind(part, "no_such_kind")
    with pytest.raises(LookupError,
                       match=f"bench/layers/{part}.no_such_kind.py") as e:
        if use == "reference":
            params = make_params(load_json(DATA / "tiny-dense.json"), 1)
            toks = jnp.zeros((1, 64), jnp.int32)
            reference.logits(config["model"], params, toks, toks[:, :4])
        else:
            flops.prefill(config, 16)
    assert e.type is LookupError


@pytest.mark.parametrize("ffn_kind", ["none", "toy"])
def test_a_kind_is_added_by_adding_a_file(toy_dir, ffn_kind):
    import jax.numpy as jnp

    config = _toy_config(ffn_kind)
    d, V, reps = 8, 16, 2
    params = _toy_params(d, V, reps)
    toks = np.arange(64, dtype=np.int32)[None] % V
    read = np.array([[0, 5, 63]], np.int32)
    got = np.asarray(reference.logits(config["model"], params,
                                      jnp.asarray(toks), jnp.asarray(read)))
    b = params["groups"][0]["stacked"]["b0"]
    x = params["embed"][toks].astype(np.float64)
    for r in range(reps):
        x = x + 0.5 * _rms(x, b["ln1"][r]) @ b["mixer"]["w"][r]
        if ffn_kind == "toy":
            x = x + 0.25 * _rms(x, b["ln2"][r]) @ b["ffn"]["w"][r]
    want = _rms(x[0, read[0]], params["final_norm"]) @ params["unembed"]
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-5)

    parts = 1 + (ffn_kind == "toy")
    assert flops.matmul_params(config) == reps * parts * d * d
    tok = 2.0 * reps * parts * d * d + reps * parts * 7.0
    head = 2.0 * d * V
    assert flops.prefill(config, 3) == (3 * tok + 6 * reps * parts * 3.0
                                        + head)
    assert flops.decode_block(config, [4], 2) == (2 * (tok + head)
                                                  + 11 * reps * parts * 5.0)


@pytest.mark.parametrize("use", ["forward", "pair_flops"])
def test_full_attention_refuses_a_window(use):
    import jax.numpy as jnp

    config = _with_kind("mixer", "full")
    spec = dict(config["model"]["blocks"]["attn"]["mixer"], window=32)
    full = layers.module("mixer", "full")
    with pytest.raises(ValueError, match="window=32"):
        if use == "forward":
            full.forward({}, jnp.zeros((1, 64, 64)), spec, eps=1e-5,
                         low=False)
        else:
            config["model"]["blocks"]["attn"]["mixer"] = spec
            flops.prefill(config, 16)


@pytest.mark.parametrize("kind,want", [("attention", ["attn"]),
                                       ("full", ["attn"]),
                                       ("mla", ["mla"]),
                                       ("mamba2", [])])
def test_shapes_layers_keeps_mla_apart_from_attention(kind, want):
    config = copy.deepcopy(load_json(DATA / "tiny-dense.json"))
    m = config["model"]
    m["blocks"]["mla"] = {
        "mixer": {"type": "attention", "kind": "mla", "q_heads": 4,
                  "kv_heads": 4, "head_dim": 16, "mla_kv_rank": 32,
                  "mla_rope_dim": 8},
        "ffn": {"kind": "none"}}
    m["groups"].append({"blocks": ["mla"], "repeats": 3})
    names = {id(b): n for n, b in m["blocks"].items()}
    got = [(names[id(b)], reps) for b, reps in shapes.layers(config, kind)]
    reps = {"attn": m["groups"][0]["repeats"], "mla": 3}
    assert got == [(n, reps[n]) for n in want]


@pytest.mark.parametrize("name", LAYER_FILES)
def test_every_layer_file_keeps_the_contract(name):
    part, kind = name.split(".")
    assert part in ("mixer", "ffn")
    mod = layers.module(part, kind)
    sig = inspect.signature(mod.forward)
    assert list(sig.parameters)[:3] == ["p", "x", "spec"]
    assert all(sig.parameters[k].kind is inspect.Parameter.KEYWORD_ONLY
               for k in ("eps", "low"))
    assert list(inspect.signature(mod.matmul_params).parameters) == [
        "spec", "d_model"]
    for fn, args in (("state_flops", ["spec"]),
                     ("pair_flops", ["spec", "phase"])):
        if hasattr(mod, fn):
            assert list(inspect.signature(getattr(mod, fn)).parameters) \
                == args
