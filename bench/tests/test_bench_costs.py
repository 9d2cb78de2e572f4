"""Each kernel cost file and the model FLOPs against hand-computed cases."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import flops, peaks
from bench.context import Context
from bench.driver import Rec, Records
from bench.spec import load_json

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"


def _kernel(name):
    spec = importlib.util.spec_from_file_location(
        f"k_{name}", BENCH / "kernels" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ctx(config, **rec):
    r = Records(**rec)
    r.trace_window = (0.0, 10.0)
    r.window = (0.0, 10.0)
    return Context(config, r)


@pytest.fixture(scope="module")
def hybrid():
    return load_json(DATA / "tiny-hybrid.json")


def test_flash_attn_hand_case():
    k = _kernel("flash_attn")
    # B=1, H=2, Hkv=1, D=4, Sq=Sk=3: causal pairs 1+2+3 = 6
    f, b = k.call(1, 2, 1, 4, 3, 3)
    assert f == 4 * 1 * 2 * 4 * 6
    assert b == 2 * 4 * 1 * (2 * 2 * 3 + 2 * 1 * 3)
    # chunk 1 of C=2: Sq=2 over Sk=4 -> pairs 2*2 + 3 = 7
    f, _ = k.call(1, 2, 1, 4, 2, 4)
    assert f == 4 * 2 * 4 * 7


def test_flash_attn_cost_counts_units_and_chunks(hybrid):
    k = _kernel("flash_attn")
    m = hybrid["model"]["blocks"]["shared_attn"]["mixer"]
    ctx = _ctx(hybrid,
               units=[{"t0": 1.0, "lengths": np.array([10, 20, 30]),
                       "bucket": 32}],
               chunks=[{"t0": 2.0, "index": 1, "chunk": 64, "batch": 1,
                        "lengths": np.array([100])},
                       {"t0": 20.0, "index": 0, "chunk": 64, "batch": 1,
                        "lengths": np.array([100])}])
    f, b = k.cost(ctx)
    H, Hkv, D, reps = m["q_heads"], m["kv_heads"], m["head_dim"], 2
    f1, b1 = k.call(4, H, Hkv, D, 32, 32)        # batch 3 pads to 4
    f2, b2 = k.call(1, H, Hkv, D, 64, 128)
    assert f == reps * (f1 + f2) and b == reps * (b1 + b2)


def test_decode_attn_hand_case(hybrid):
    k = _kernel("decode_attn")
    m = hybrid["model"]["blocks"]["shared_attn"]["mixer"]
    ctx = _ctx(hybrid, blocks=[{"t0": 1.0, "block": 2, "slots": 4,
                                "active": np.array([True, False, True,
                                                    False]),
                                "lengths": np.array([5, 9, 10, 0])}])
    f, b = k.cost(ctx)
    # active slots at 5 and 10 cached tokens, 2 steps: keys (6+7) + (11+12)
    keys = 36
    H, Hkv, D = m["q_heads"], m["kv_heads"], m["head_dim"]
    assert f == 2 * 4.0 * H * D * keys
    assert b == 2 * 2 * D * (2 * Hkv * keys + 2 * H * 2 * 2)


def test_gla_hand_case():
    k = _kernel("gla")
    f, b = k.call(1, 1, 64, 2, 3)
    assert f == 64 * 65 * (2 + 3) + 4.0 * 64 * 2 * 3
    assert b == 2 * 64 * (2 * 2 + 2 * 3) + 4 * 64 + 2 * 4 * 2 * 3


def test_quantize_counts_offloaded_requests_in_the_trace(hybrid):
    k = _kernel("quantize")
    reqs = {1: Rec(1, 0.0, 100, 5, "window", route="prfaas", first=1.0),
            2: Rec(2, 0.0, 50, 5, "window", route="pd", first=1.0),
            3: Rec(3, 0.0, 70, 5, "window", route="prfaas", first=30.0)}
    f, b = k.cost(_ctx(hybrid, reqs=reqs))
    m = hybrid["model"]["blocks"]["shared_attn"]["mixer"]
    n = 2 * 2 * 100 * m["kv_heads"] * m["head_dim"]
    assert (f, b) == (3.0 * n, 5.0 * n)


def test_model_flops_hand_case(hybrid):
    d = hybrid["model"]["d_model"]
    # 4 mamba layers and 2 invocations of the shared attention+FFN block
    mamba = d * 2 * (2 * 16 + 32) + d * 2 + d * 2 * 32 + 2 * 32 * d
    attn = d * 4 * 16 * 2 + d * 2 * 16 * 2 + 3 * d * 128
    assert flops.matmul_params(hybrid) == 5 * mamba + 2 * attn
    tok = 2.0 * (5 * mamba + 2 * attn) + 5 * (4.0 * 2 * 16 * 32
                                             + 2.0 * 4 * 2 * 64)
    pair = 2 * 4.0 * 4 * 16
    head = 2.0 * d * hybrid["model"]["vocab_size"]
    assert flops.prefill(hybrid, 3) == 3 * tok + 6 * pair + head
    assert flops.decode_block(hybrid, [4], 2) == 2 * (tok + head) + 11 * pair


def test_least_seconds_picks_the_binding_peak():
    p = peaks.peaks("TPU v5 lite")
    assert peaks.least_seconds(197e12, 0.0, p) == pytest.approx(1.0)
    assert peaks.least_seconds(0.0, 819e9, p) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.peaks("no such chip")


def test_roofline_reader_is_silent_without_trace(hybrid):
    ctx = Context(hybrid, SimpleNamespace(units=[], chunks=[], blocks=[],
                                          reqs={}, trace_window=None))
    assert ctx.roofline("flash_attn") is None
