"""The serving path's spans (``serving.spans``) and the wall stamps on
``Request``: nesting, the bounded ring, durations, compile crediting, and
the order and use of the stamps in a live deployment."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import spans


def _since(t0):
    return [s for s in spans.recent() if s[1] >= t0]


def test_nested_spans_land_in_the_ring_innermost_first():
    t0 = time.perf_counter()
    with spans.span("test.outer", k=1) as outer:
        with spans.span("test.inner") as inner:
            time.sleep(0.002)
        inner_s = inner.seconds
        assert outer.t1 is None and outer.seconds >= inner_s
    got = _since(t0)
    assert [s[0] for s in got] == ["test.inner", "test.outer"]
    (_, i0, i1, _), (_, o0, o1, attrs) = got
    assert o0 <= i0 <= i1 <= o1
    assert attrs == {"k": 1}
    assert inner.seconds == pytest.approx(i1 - i0) and inner_s >= 0.002
    assert outer.seconds == pytest.approx(o1 - o0)
    assert inner.seconds == inner_s          # a closed span keeps its time


def test_attrs_set_inside_the_span_are_kept():
    t0 = time.perf_counter()
    with spans.span("test.attrs", rid=3) as sp:
        sp.attrs["bytes"] = 10
    assert _since(t0)[-1][3] == {"rid": 3, "bytes": 10}


def test_a_raising_body_still_closes_its_span():
    t0 = time.perf_counter()
    with pytest.raises(ValueError):
        with spans.span("test.raises"):
            raise ValueError("inside")
    assert [s[0] for s in _since(t0)] == ["test.raises"]
    with spans.span("test.after"):
        pass
    assert _since(t0)[-1][0] == "test.after"


def test_the_ring_is_bounded():
    for _ in range(spans.MAXLEN + 10):
        with spans.span("test.fill"):
            pass
    ring = spans.recent()
    assert len(ring) == spans.MAXLEN
    assert all(s[0] == "test.fill" for s in ring)
    assert all(a[1] <= b[1] for a, b in zip(ring, ring[1:]))


def test_a_compile_is_credited_to_the_innermost_open_span():
    before = spans.compiles()
    width = 17 + int(time.perf_counter_ns() % 1000)   # a shape never seen
    with spans.span("test.compile_outer"):
        with spans.span("test.compile_inner"):
            jax.jit(lambda x: jnp.sin(x) * 3.0)(
                jnp.ones((width,))).block_until_ready()
    after = spans.compiles()
    n, s = after["test.compile_inner"]
    n0, s0 = before.get("test.compile_inner", (0, 0.0))
    assert n > n0 and s > s0
    assert after.get("test.compile_outer") == before.get("test.compile_outer")


@pytest.mark.slow
def test_request_stamps_follow_the_request_through_the_deployment():
    """One offloaded (int8 wire) and one local request through
    ``submit_batch``: every stamp is set, in the order of the request's
    life; the wire span names the offloaded request and its bytes; the
    decode engine's time between tokens counts from the first token; the
    deployment's TTFT is the stamps' wall and the link's virtual-clock
    exposure is reported apart from it."""
    from repro.configs import get_smoke_config
    from repro.models import Model
    from repro.serving import CrossDCDeployment, DeploymentConfig, Request

    cfg = get_smoke_config("qwen2.5-3b")
    model = Model(cfg, use_kernels=False)
    params = model.init(jax.random.PRNGKey(0))
    dep = CrossDCDeployment(model, params, DeploymentConfig(
        threshold=48, capacity=256, decode_slots=2, link_gbps=0.01,
        wire_compression=True))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, tokens=rng.integers(0, cfg.vocab_size, (L,))
                    .astype(np.int32), max_new_tokens=5)
            for i, L in enumerate([16, 100])]
    t0 = time.perf_counter()
    out = dep.submit_batch(reqs)
    assert [r.route for r in reqs] == ["pd", "prfaas"]
    assert all(out[r.rid].finished for r in reqs)
    for r in reqs:
        assert t0 <= r.t_submit <= r.t_unit_start <= r.t_first \
            <= r.t_admit <= r.t_finish, r.rid
    dec = dep.decoders["pd"]
    assert sorted(dec.tbt_s) == pytest.approx(sorted(
        (r.t_finish - r.t_first) / (len(out[r.rid].output_tokens) - 1)
        for r in reqs))

    got = _since(t0)
    wire = [s for s in got if s[0] == "prfaas.wire"]
    assert len(wire) == 1 and wire[0][3] == {"rid": 1,
                                            "bytes": reqs[1].kv_bytes}
    names = {s[0] for s in got}
    assert {"prfaas.route", "prfaas.tick", "prfaas.admit",
            "prfaas.prefill.unit", "prfaas.unit_done",
            "prfaas.decode.block", "prfaas.decode.dispatch",
            "prfaas.decode.sync", "prfaas.decode.bookkeep"} <= names
    unit_done = [s for s in got if s[0] == "prfaas.unit_done"]
    assert any(u0 <= wire[0][1] and wire[0][2] <= u1
               for _, u0, u1, _ in unit_done)
    # the decode block's phases nest in it, back to back
    blocks = [s for s in got if s[0] == "prfaas.decode.block"]
    phases = [s for s in got if s[0] in ("prfaas.decode.dispatch",
                                         "prfaas.decode.sync",
                                         "prfaas.decode.bookkeep")]
    assert len(phases) == 3 * len(blocks)
    for (_, b0, b1, _), three in zip(blocks, zip(*[iter(phases)] * 3)):
        assert [p[0].rsplit(".", 1)[1] for p in three] == [
            "dispatch", "sync", "bookkeep"]
        assert b0 <= three[0][1] and three[2][2] <= b1
        assert three[0][2] <= three[1][1] and three[1][2] <= three[2][1]
    # the walls the engines keep are read from their spans
    ticks = [s for s in got if s[0] == "prfaas.tick"]
    assert dep.schedulers["pd"].wall_s == pytest.approx(
        sum(b - a for _, a, b, _ in ticks))

    m = dep.metrics()
    ttft = np.mean([r.t_first - r.t_submit for r in reqs])
    assert m["ttft_mean_s"] == pytest.approx(ttft)
    assert m["clusters"]["pd"]["ttft_mean_s"] == pytest.approx(ttft)
    assert m["link_exposed_s_mean"] == pytest.approx(
        np.mean([r.transfer_s for r in reqs]))
    assert reqs[1].transfer_s > 0 and reqs[0].transfer_s == 0
