"""Vectorized SoA engine (``SimConfig(engine="vector")``): equivalence to
the exact event engine — pinned scenario + property-style over randomized
control-plane configs — plus byte conservation on the vectorized link
solver, epoch-grid snapping, and the trace-driven workload layer."""
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st
from repro.core import (PRFAAS, PrfaasSimulator, SimConfig, ThroughputModel,
                        Trace, Workload, conversation_trace, diurnal_trace,
                        flash_crowd_trace, paper_h20_profile,
                        paper_h200_profile)

_EQ_KEYS = ("throughput_rps", "ttft_mean", "ttft_p90", "offload_frac",
            "egress_gbps")

_SETUP: list = []             # lazy module cache (fixtures can't mix with
                              # @given under the hypothesis fallback shim)


def _setup():
    if not _SETUP:
        w = Workload(session_prob=0.35, burst_factor=1.6)
        tm = ThroughputModel(paper_h200_profile(), paper_h20_profile(), w)
        sc, lam, _ = tm.grid_search(6, 12, 100e9 / 8)
        _SETUP.append((tm, sc, lam, w))
    return _SETUP[0]


def _run(tm, sc, w, engine, **kw):
    return PrfaasSimulator(tm, sc, w, SimConfig(engine=engine, **kw)).run()


def _assert_close(v, e, keys=_EQ_KEYS, rel=0.05):
    for k in keys:
        assert v[k] == pytest.approx(e[k], rel=rel, abs=1e-9), k


# --------------------------------------------------------------------------
# event vs vector equivalence
# --------------------------------------------------------------------------
class TestVectorEquivalence:
    def test_pinned_scenario_within_5pct(self):
        """The pinned two-cluster scenario (sessions + bursts + OU link
        noise on a congested 25 Gbps star) must agree with the exact
        engine on every headline metric."""
        w = Workload(session_prob=0.3, burst_factor=1.5)
        tm = ThroughputModel(paper_h200_profile(), paper_h20_profile(), w)
        sc, lam, _ = tm.grid_search(4, 8, 100e9 / 8)
        kw = dict(arrival_rate=0.8 * lam, sim_time=360, dt=0.02, seed=11,
                  link_gbps=25.0, link_fluctuation=0.15, vector_dt=0.05)
        e = _run(tm, sc, w, "event", **kw)
        v = _run(tm, sc, w, "vector", **kw)
        _assert_close(v, e)

    @settings(max_examples=8, deadline=None)
    @given(st.integers(1, 4),                    # regional PD clusters
           st.integers(0, 1000),                 # seed
           st.sampled_from([0.0, 0.15, 0.3]),    # session roaming
           st.sampled_from([0.0, 0.1, 0.2]),     # OU link fluctuation
           st.sampled_from([0.0, 8.0]),          # PD<->PD mesh Gbps
           st.sampled_from([0, 8]),              # decode block tokens
           st.sampled_from([0.6, 0.85]),         # load fraction
           st.booleans())                        # regional autoscaling
    def test_randomized_configs_within_5pct(self, k, seed, roam, fluct,
                                            mesh, dbt, load, autoscale):
        """Property-style: random topology / roaming / autoscale /
        block-granularity configs from the supported envelope must stay in
        the 5% equivalence band on every headline metric."""
        tm, sc, lam, w = _setup()
        kw = dict(arrival_rate=load * lam, sim_time=240, dt=0.02, seed=seed,
                  link_gbps=25.0, link_fluctuation=fluct, vector_dt=0.05,
                  decode_block_tokens=dbt, autoscale=autoscale,
                  pd_clusters=k, pd_mesh_gbps=mesh if k > 1 else 0.0,
                  roam_prob=roam if k > 1 else 0.0)
        e = _run(tm, sc, w, "event", **kw)
        v = _run(tm, sc, w, "vector", **kw)
        _assert_close(v, e)

    def test_slo_metrics_match_event_engine(self):
        """With a TTFT SLO set, attainment/goodput keys exist in both
        engines and agree on an uncongested scenario."""
        tm, sc, lam, w = _setup()
        kw = dict(arrival_rate=0.6 * lam, sim_time=240, seed=3,
                  vector_dt=0.05, ttft_slo_s=4.0)
        e = _run(tm, sc, w, "event", **kw)
        v = _run(tm, sc, w, "vector", **kw)
        assert v["ttft_slo_s"] == e["ttft_slo_s"] == 4.0
        assert v["slo_attainment"] == pytest.approx(e["slo_attainment"],
                                                    abs=0.05)
        assert v["goodput_rps"] == pytest.approx(e["goodput_rps"], rel=0.05)


# --------------------------------------------------------------------------
# vectorized link solver: bytes sent == bytes charged by routing decisions
# --------------------------------------------------------------------------
class TestVectorLinkConservation:
    def test_bytes_sent_equal_bytes_charged(self):
        """Replay a roaming conversation trace whose arrivals all land in
        the first quarter of the horizon (long drain tail): after the run,
        every pair link's fluid-solver sent bytes must equal the KV bytes
        the routing decisions charged to that pair, and no backlog may
        linger."""
        tm, sc, lam, w = _setup()
        names = ("pd0", "pd1", "pd2")
        starts = diurnal_trace(0.1 * lam, 60.0, seed=5, depth=0.0).arrival
        tr = conversation_trace(starts, 200.0, seed=5, home_names=names,
                                turns_mean=3.0, think_mean_s=10.0,
                                roam_prob=0.3)
        sim = PrfaasSimulator(tm, sc, w, SimConfig(
            arrival_rate=1.0, sim_time=400.0, seed=5, engine="vector",
            vector_dt=0.05, pd_clusters=3, pd_mesh_gbps=8.0,
            pool_blocks=2_000_000))
        sim.inject_soa_trace(tr)
        sim.run()
        eng = sim._vector_state
        prof = tm.prfaas_profile

        def s_kv(tok):
            return prof.s_kv(tok)

        charged = {}

        def charge(a, b, nb):
            key = f"{min(a, b)}|{max(a, b)}"
            charged[key] = charged.get(key, 0.0) + nb

        started = eng.pf_start >= 0
        for i in np.flatnonzero(started):
            tgt = eng.names[eng.target[i]]
            home = eng.names[1 + eng.home[i]]
            cached = int(eng.cached[i])
            if tgt == PRFAAS:
                nb = s_kv(int(eng.total[i]))
                if cached:
                    nb -= s_kv(cached)
                charge(PRFAAS, home, max(nb, 1.0))
            if eng.cross[i] and cached:
                charge(eng.names[eng.cache_cl[i]], tgt,
                       max(s_kv(cached), 1.0))
        for (a, b), L in zip(eng.link_keys, eng.links):
            pair = f"{min(a, b)}|{max(a, b)}"
            assert L.backlog == pytest.approx(0.0, abs=1e-3), pair
            assert L.S == pytest.approx(charged.get(pair, 0.0),
                                        rel=1e-6, abs=1.0), pair

    def test_epoch_grid_snaps_to_control_grid(self):
        """``vector_dt`` must land on a divisor (or multiple) of
        ``control_dt`` so routing signals are sampled at the control
        instants — misaligned grids systematically skew route decisions."""
        from repro.core.vector_engine import _VectorEngine
        tm, sc, lam, w = _setup()

        def eng(vdt, cdt=0.25):
            sim = PrfaasSimulator(tm, sc, w, SimConfig(
                arrival_rate=1.0, sim_time=10.0, engine="vector",
                vector_dt=vdt, control_dt=cdt))
            return _VectorEngine(sim)

        assert eng(0.11).dt == pytest.approx(0.125)   # 0.25 / 2
        assert eng(0.05).dt == pytest.approx(0.05)    # already a divisor
        assert eng(0.6).dt == pytest.approx(0.5)      # 0.25 * 2
        assert eng(1.0).dt == pytest.approx(1.0)      # 0.25 * 4


# --------------------------------------------------------------------------
# trace-driven workload layer
# --------------------------------------------------------------------------
class TestTraceLayer:
    def test_save_load_round_trip(self, tmp_path):
        tr = diurnal_trace(2.0, 300.0, seed=9,
                           home_names=("pd0", "pd1"), shares=(0.7, 0.3),
                           tz_offsets_s=(0.0, 150.0), day_s=300.0)
        path = str(tmp_path / "trace.npz")
        tr.save(path)
        back = Trace.load(path)
        np.testing.assert_array_equal(tr.arrival, back.arrival)
        np.testing.assert_array_equal(tr.total_len, back.total_len)
        np.testing.assert_array_equal(tr.session, back.session)
        np.testing.assert_array_equal(tr.home, back.home)
        assert back.home_names == ("pd0", "pd1")
        assert back.meta["family"] == "diurnal"
        assert back.meta["seed"] == 9

    def test_diurnal_mean_rate_and_phases(self):
        tr = diurnal_trace(5.0, 2000.0, seed=1,
                           home_names=("a", "b"), tz_offsets_s=(0.0, 1000.0),
                           day_s=2000.0)
        assert len(tr) / 2000.0 == pytest.approx(5.0, rel=0.1)
        # opposite phase: region a peaks in the first half-day, b in the
        # second (tz offset = half a day)
        a_first = (tr.arrival[tr.home == 0] < 1000.0).mean()
        b_first = (tr.arrival[tr.home == 1] < 1000.0).mean()
        assert a_first > 0.55 > 0.45 > b_first

    def test_flash_crowd_spikes_local_rate(self):
        tr = flash_crowd_trace(2.0, 600.0, seed=2, flash_times=(300.0,),
                               flash_amp=4.0, flash_decay_s=30.0)
        during = ((tr.arrival >= 300.0) & (tr.arrival < 330.0)).sum() / 30.0
        before = ((tr.arrival >= 200.0) & (tr.arrival < 290.0)).sum() / 90.0
        assert during > 2.0 * before
        assert tr.meta["family"] == "flash_crowd"

    def test_conversation_sessions_grow_and_gap(self):
        starts = np.arange(0.0, 100.0, 5.0)
        tr = conversation_trace(starts, 10_000.0, seed=3, turns_mean=5.0,
                                think_mean_s=30.0)
        assert tr.n_sessions == len(starts)
        for s in range(tr.n_sessions):
            m = tr.session == s
            assert np.all(np.diff(tr.arrival[m]) > 0.0)       # think gaps
            assert np.all(np.diff(tr.total_len[m]) >= 0.0)    # ctx grows
        # mean turns per session ~ geometric(1/5)
        assert len(tr) / tr.n_sessions == pytest.approx(5.0, rel=0.35)

    def test_conversation_roaming_rehomes_turns_not_sessions(self):
        starts = np.arange(0.0, 200.0, 2.0)
        tr = conversation_trace(starts, 10_000.0, seed=4,
                                home_names=("x", "y", "z"), turns_mean=6.0,
                                roam_prob=0.4)
        moved = 0
        for s in range(tr.n_sessions):
            h = tr.home[tr.session == s]
            moved += int((np.diff(h) != 0).sum())
        assert moved > 0
        tr0 = conversation_trace(starts, 10_000.0, seed=4,
                                 home_names=("x", "y", "z"), turns_mean=6.0,
                                 roam_prob=0.0)
        for s in range(tr0.n_sessions):
            h = tr0.home[tr0.session == s]
            assert np.all(h == h[0])

    def test_trace_validation_rejects_bad_columns(self):
        with pytest.raises(ValueError, match="sorted"):
            Trace(np.array([1.0, 0.5]), np.array([10, 10]),
                  np.array([0, 1]), np.array([0, 0]))
        with pytest.raises(ValueError, match="equal length"):
            Trace(np.array([1.0]), np.array([10, 10]),
                  np.array([0]), np.array([0]))
        with pytest.raises(ValueError, match="home index"):
            Trace(np.array([1.0]), np.array([10]),
                  np.array([0]), np.array([2]), home_names=("pd",))

    def test_soa_trace_replay_matches_event_replay(self):
        """The same trace replayed through the vector engine (SoA fast
        path) and the event engine (object path) must agree within the
        equivalence band."""
        tm, sc, lam, w = _setup()
        names = ("pd0", "pd1")
        tr = diurnal_trace(0.5 * lam, 240.0, seed=6, home_names=names,
                           tz_offsets_s=(0.0, 120.0), day_s=240.0)
        out = {}
        for engine in ("event", "vector"):
            sim = PrfaasSimulator(tm, sc, w, SimConfig(
                arrival_rate=0.5 * lam, sim_time=240.0, seed=6,
                engine=engine, vector_dt=0.05, pd_clusters=2))
            sim.inject_soa_trace(tr)
            out[engine] = sim.run()
        _assert_close(out["vector"], out["event"],
                      keys=("throughput_rps", "ttft_mean", "ttft_p90"))
