"""The harness end to end on the CPU at toy widths: the open-loop driver
serves the tiny hybrid through the program's ``CrossDCDeployment`` (both
routes, chunked prefill, the int8 wire), the run prints a well-formed
line, and ``correct`` comes out false when the timed path is broken
underneath or when the float8 control stands in for it."""
import json
import time
from pathlib import Path

import pytest

from bench import spec
from bench.run import run_cell

DATA = Path(__file__).resolve().parent / "data"
SEED = 7
SECONDS = 2.0


def _cell(trace=False):
    b = spec.load_benchmark()
    return spec.Cell(
        "tiny-hybrid.tiny_mix", spec.load_json(DATA / "tiny-hybrid.json"),
        "tiny_mix", spec.load_json(DATA / "tiny_mix.json"), 6.0, 1,
        b["end_to_end"], b["per_layer"])


def _run(trace=False, control=False):
    return run_cell(_cell(), SEED, SECONDS, trace, require_tpu=False,
                    warm_s=0.5, post_s=30.0, trace_s=1.0,
                    t_start=time.perf_counter(), control=control,
                    compile_cache=False)


@pytest.fixture(scope="module")
def sound():
    return _run(control=True)


def test_line_is_well_formed_and_correct(sound):
    result, compared, readings = sound
    line = json.loads(json.dumps(result))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 5
    assert set(line["metrics"]) == {
        m["name"] for m in spec.load_benchmark()["end_to_end"]}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert compared["logit_gap"]["value"] <= compared["logit_gap"]["limit"]
    assert readings["tokens_compared"] >= 20


def test_control_fails_the_limit(sound):
    """float8 in place of the program reads a gap past the limit, and the
    comparison that decides ``correct`` calls it not correct."""
    from bench import check

    _, compared, readings = sound
    assert readings["control_gap"] > compared["logit_gap"]["limit"]
    correct, control = check.control_verdict(
        readings, compared["logit_gap"]["limit"],
        compared["unfinished"]["value"])
    assert correct is False
    assert control["logit_gap"]["value"] == readings["control_gap"]


def test_decode_step_that_keeps_its_state_is_caught(monkeypatch):
    from repro.models.model import Model

    inner = Model.decode_step

    def stale(self, params, tokens, caches, lengths, **kw):
        logits, _ = inner(self, params, tokens, caches, lengths, **kw)
        return logits, caches

    monkeypatch.setattr(Model, "decode_step", stale)
    result, compared, _ = _run()
    assert result["correct"] is False
    assert compared["logit_gap"]["value"] > compared["logit_gap"]["limit"]


def test_token_altered_where_it_is_produced_is_caught(monkeypatch):
    import jax.numpy as jnp

    from repro.serving.engine import DecodeEngine

    inner = DecodeEngine._select

    def altered(self, logits, key):
        nxt = inner(self, logits, key)
        return nxt.at[0].set((nxt[0] + 1) % logits.shape[-1]).astype(
            jnp.int32)

    monkeypatch.setattr(DecodeEngine, "_select", altered)
    result, compared, _ = _run()
    assert result["correct"] is False
    assert compared["logit_gap"]["value"] > compared["logit_gap"]["limit"]


def test_traced_run_reads_the_host_before_the_profiler(monkeypatch):
    """``--trace 1``: the profiler starts at the due time of the last
    window request due ``trace_s`` or more before the window's end, and
    the host-clock metrics read only the part of the window before it
    (starting the profiler stalls the host).  The CPU's trace has no
    device plane, so no device metric is read."""
    from bench.driver import OpenLoop

    seen = {}
    inner = OpenLoop.run

    def run(self, *a, **kw):
        seen["rec"] = rec = inner(self, *a, **kw)
        seen["arrivals"] = self.arrivals
        seen["t0"] = self.t0
        return rec

    monkeypatch.setattr(OpenLoop, "run", run)
    result, _, _ = _run(trace=True)
    rec = seen["rec"]
    w0, w1 = rec.window
    due = max(seen["t0"] + a.due_s for a in seen["arrivals"]
              if a.segment == "window" and seen["t0"] + a.due_s <= w1 - 1.0)
    assert w0 < due <= rec.host_span[1] <= rec.trace_window[0] < w1
    assert rec.host_span[0] == w0
    m = result["metrics"]
    assert {"sched.queue_wait_ms_p90", "decode.block_ms",
            "link_mb_per_req"} <= set(m)
    assert not {"mfu.decode", "decode_attn_roofline",
                "device.idle_share"} & set(m)
    assert result["device"]["window_s"] >= 1.0
    assert result["correct"] is True
