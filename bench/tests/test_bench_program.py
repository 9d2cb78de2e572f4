"""The program's ``prfaas.*`` spans in a trace: idle gaps named by them
(``bench/program_trace.py``), the reduction's existing outputs untouched by
them, their stamps on the trace's clock, and the wire reader."""
import sys
import time
from types import SimpleNamespace

import pytest

from bench import program_trace, trace
from bench.tests.test_bench_trace import _recorded

OLD_KEYS = ("busy_s", "span_s", "ops", "modules", "gaps", "waiting_s",
            "devices")


def _synthetic():
    return {"devices": [{"name": "/device:TPU:0",
                         "ops": [("fusion.1", 0.0, 1.0),
                                 ("_flash_kernel", 1.0, 1.5),
                                 ("fusion.7", 3.0, 4.0),
                                 ("fusion.8", 4.5, 5.0)],
                         "modules": [("jit__prefill_impl(1)", 0.0, 1.5),
                                     ("jit__block_impl(2)", 3.0, 4.0)]}],
            "host": [("bench.tick", 0.0, 5.0),
                     ("bench.decode_block", 2.5, 5.0),
                     ("bench.admit", 1.6, 2.2)]}


def _assert_old_keys_unchanged(raw, program, **kw):
    plain = trace.reduce(raw, **kw)
    with_program = trace.reduce(dict(raw, program=program), **kw)
    for key in OLD_KEYS:
        assert repr(with_program[key]) == repr(plain[key]), key
    return plain


def test_gaps_are_named_by_the_innermost_program_span():
    raw = _synthetic()
    program = [("prfaas.tick", 0.0, 5.0),
               ("prfaas.decode.dispatch", 2.0, 2.3),
               ("prfaas.decode.sync", 2.3, 4.2)]
    r = _assert_old_keys_unchanged(raw, program)
    # gaps 1.5 -> 3.0 (midpoint 2.25) and 4.0 -> 4.5 (midpoint 4.25)
    assert r["gaps"] == [("bench.tick", 1.5), ("bench.decode_block", 0.5)]
    assert program_trace.program_gaps(raw, program, r) == [
        ("prfaas.decode.dispatch", 1.5), ("prfaas.tick", 0.5)]
    # no program span holds the second midpoint: the gap keeps its label
    assert program_trace.program_gaps(raw, program[1:], r) == [
        ("prfaas.decode.dispatch", 1.5), ("bench.decode_block", 0.5)]
    assert program_trace.program_gaps(raw, [], r) == r["gaps"]
    assert program_trace.program_gaps({"devices": [], "host": []}, program,
                                      trace.reduce({"devices": [],
                                                    "host": []})) == []


def test_nesting_holds_over_siblings_that_ended():
    raw = _synthetic()
    program = [("prfaas.tick", 0.0, 5.0),
               ("prfaas.admit", 0.2, 0.3),
               ("prfaas.prefill.unit", 0.4, 1.7),
               ("prfaas.decode.bookkeep", 3.9, 4.2)]
    r = trace.reduce(raw)
    assert program_trace.program_gaps(raw, program, r) == [
        ("prfaas.tick", 1.5), ("prfaas.tick", 0.5)]


def test_recorded_tick_keeps_its_reduction():
    """The tick recorded on a TPU v5e: with program spans beside it, every
    existing output of the reduction is unchanged, and the largest gap is
    named by the program span that holds it."""
    from bench.context import kernel_namer

    raw = _recorded()
    r = trace.reduce(raw, kernel_of=kernel_namer())
    idle = trace.idle_intervals(
        [(x, y) for _, x, y in raw["devices"][0]["ops"]],
        min(t for _, a, b in raw["devices"][0]["ops"] + raw["host"]
            for t in (a, b)),
        max(t for _, a, b in raw["devices"][0]["ops"] + raw["host"]
            for t in (a, b)))
    a, b = max(idle, key=lambda iv: iv[1] - iv[0])
    program = [("prfaas.tick", idle[0][0], idle[-1][1]),
               ("prfaas.decode.sync", a, b)]
    _assert_old_keys_unchanged(raw, program, kernel_of=kernel_namer())
    named = program_trace.program_gaps(raw, program, r)
    assert [s for _, s in named] == [s for _, s in r["gaps"]]
    assert dict(named)["prfaas.decode.sync"] == pytest.approx(b - a)
    assert {label for label, _ in named} == {"prfaas.tick",
                                             "prfaas.decode.sync"}


def test_program_spans_are_on_the_trace_clock(tmp_path):
    """A span's ring stamps, moved by one offset, land on its host event
    in the profiler's trace."""
    import jax

    from repro.serving import spans

    t0 = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for name in ("prfaas.test.mark", "prfaas.test.a", "prfaas.test.b"):
            with spans.span(name):
                time.sleep(0.003)
    finally:
        jax.profiler.stop_trace()
    ring = [s for s in spans.recent()
            if s[1] >= t0 and s[0].startswith("prfaas.test.")]
    events = [e for e in program_trace.load(trace.find(str(tmp_path)))
              if e[0].startswith("prfaas.test.")]
    assert [e[0] for e in events] == [s[0] for s in ring]
    offset = events[0][1] - ring[0][1]
    for (_, a, b, _), (_, x, y) in zip(ring, events):
        assert abs(a + offset - x) < 2e-4 and abs(b + offset - y) < 2e-4


def _ctx(host_span):
    return SimpleNamespace(in_host_span=lambda t: host_span[0] <= t
                           < host_span[1])


def test_wire_reader(monkeypatch):
    from bench.spec import metric_reader
    from repro.serving import spans

    read = metric_reader("wire.host_ms_per_offload")
    ring = [("prfaas.wire", 1.0, 1.004, {"rid": 1, "bytes": 10}),
            ("prfaas.unit_done", 0.9, 1.1, {}),
            ("prfaas.wire", 2.0, 2.002, {"rid": 2, "bytes": 10}),
            ("prfaas.wire", 9.0, 9.1, {"rid": 3, "bytes": 10})]
    monkeypatch.setattr(spans, "recent", lambda: ring)
    assert read(_ctx((0.0, 5.0))) == pytest.approx(3.0)
    assert read(_ctx((5.0, 8.0))) is None      # no offload in the host span
    monkeypatch.setattr(spans, "recent", lambda: ring[1:2])
    assert read(_ctx((0.0, 5.0))) is None


def test_wire_reader_without_the_program_spans(monkeypatch):
    """A program without ``repro.serving.spans`` reads nothing."""
    import repro.serving
    from bench.spec import metric_reader

    monkeypatch.delattr(repro.serving, "spans")
    monkeypatch.setitem(sys.modules, "repro.serving.spans", None)
    assert metric_reader("wire.host_ms_per_offload")(_ctx((0.0, 5.0))) \
        is None
