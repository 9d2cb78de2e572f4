"""Reduction of a profiler trace: busy union, per-operation and per-program
time, and idle gaps named by the host span they fall in."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_idle_intervals():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert trace.union_length(iv) == pytest.approx(3.0)
    assert trace.idle_intervals(iv, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                                   (4.0, 5.0)]


def test_names():
    assert trace.op_name("fusion.123") == "fusion"
    assert trace.op_name("_flash_kernel") == "_flash_kernel"
    assert trace.module_name("jit__block_impl(123)") == "jit__block_impl"


def test_reduce_synthetic():
    raw = {"devices": [{"name": "/device:TPU:0",
                        "ops": [("fusion.1", 0.0, 1.0),
                                ("_flash_kernel", 1.0, 1.5),
                                ("fusion.7", 3.0, 4.0)],
                        "modules": [("jit__prefill_impl(1)", 0.0, 1.5),
                                    ("jit__block_impl(2)", 3.0, 4.0)]}],
           "host": [("bench.tick", 0.0, 4.0),
                    ("bench.decode_block", 2.5, 4.0),
                    ("bench.admit", 1.6, 2.2)]}
    r = trace.reduce(raw, compiles=[(2.3, 2.5)])
    assert r["busy_s"] == pytest.approx(2.5)
    assert r["span_s"] == pytest.approx(4.0)
    assert r["ops"] == {"fusion": 2.0, "_flash_kernel": 0.5}
    assert r["modules"]["jit__prefill_impl"] == (1.5, 1)
    # one gap, 1.5 -> 3.0, midpoint 2.25: inside bench.tick only
    assert r["gaps"] == [("bench.tick", 1.5)]
    assert trace.gap_breakdown(r["gaps"]) == [["bench.tick", 1.5]]
    r = trace.reduce(raw, compiles=[(2.0, 2.4)])
    assert r["gaps"] == [("compile", 1.5)]


def _recorded():
    import gzip
    import json

    with gzip.open(DATA / "tpu_v5e_tick.json.gz", "rt") as f:
        d = json.load(f)
    texts = d["texts"]

    def events(rows):
        return [(texts[i], a, b) for i, a, b in rows]

    return {"devices": [{"name": d["device"], "ops": events(d["ops"]),
                         "modules": events(d["modules"])}],
            "host": [tuple(h) for h in d["host"]]}


def test_reduce_recorded_tick():
    """One scheduler tick recorded on a TPU v5e: a 1024-token prefill
    chunk of zamba2-1.2b, then one 8-step decode block over 4 slots."""
    from bench.context import kernel_namer

    r = trace.reduce(_recorded(), kernel_of=kernel_namer())
    assert 0 < r["busy_s"] <= r["span_s"]
    assert r["busy_s"] == pytest.approx(0.2674, abs=2e-3)
    assert r["modules"]["jit__block_impl"][1] == 1
    assert r["modules"]["jit_prefill_chunk"][1] == 1
    # every Pallas call is told apart by its signature: 6 flash calls
    # (attention layers) and 32 gla calls (Mamba2 layers) of the chunk, and
    # 8 x 6 decode-attention calls of the block
    assert "pallas" not in r["ops"]
    for kernel in ("flash_attn", "gla", "decode_attn"):
        assert r["ops"][kernel] > 0
    assert "while" not in r["ops"]
    labels = {label for label, _ in r["gaps"]}
    assert labels <= {"bench.tick", "bench.decode_block", "bench.admit",
                      "bench.prefill_unit", "bench.route", "bench.mark",
                      "host.other"}


def test_roofline_of_the_recorded_chunk_stays_under_100():
    import numpy as np

    from bench import peaks
    from bench.context import Context, kernel_namer
    from bench.driver import Records
    from bench.spec import load_json

    r = trace.reduce(_recorded(), kernel_of=kernel_namer())
    rec = Records(chunks=[{"t0": 1.0, "index": 0, "chunk": 1024,
                           "batch": 1, "lengths": np.array([3000])}])
    rec.trace_window = rec.window = (0.0, 2.0)
    ctx = Context(load_json(DATA / "zamba2-1.2b-repo.json"), rec, r,
                  peaks.peaks("TPU v5 lite"))
    for kernel in ("flash_attn", "gla"):
        share = ctx.roofline(kernel)
        assert 0 < share <= 100, (kernel, share)


def test_idle_share_leaves_out_waiting_for_arrivals():
    """Idle time in which the host had nothing to serve and waited for the
    next arrival is neither idle nor worked time."""
    from bench.context import Context
    from bench.driver import Records
    from bench.spec import metric_reader

    assert trace.overlap_length([(0.0, 1.0), (2.0, 4.0)],
                                [(0.5, 2.5), (3.0, 3.2)]) == \
        pytest.approx(0.5 + 0.5 + 0.2)
    raw = {"devices": [{"name": "/device:TPU:0",
                        "ops": [("fusion.1", 0.0, 1.0),
                                ("fusion.2", 6.0, 8.0)],
                        "modules": []}],
           "host": [("bench.tick", 0.0, 1.5),
                    ("bench.wait_arrival", 1.5, 5.0),
                    ("bench.tick", 5.0, 8.0)]}
    r = trace.reduce(raw)
    assert r["busy_s"] == pytest.approx(3.0)
    assert r["waiting_s"] == pytest.approx(3.5)
    rec = Records()
    rec.trace_window = (0.0, 8.0)
    # worked: 8 - 3.5 = 4.5 s, of which 3 busy: 1.5 / 4.5 idle
    share = metric_reader("device.idle_share")(Context({}, rec, r))
    assert share == pytest.approx(100.0 / 3)
