"""Link/transfer engine + end-to-end cluster simulator.

Includes the PR 3 property harness: for random topologies, seeds, and
roaming rates, (a) every byte a pair link reports sending was charged to
that pair by a routing decision (and vice versa), and (b) ``LinkTopology``
conserves backlog across ``advance`` — no bytes are created, lost, or
migrated between pair links by the exact solver."""
import math

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (PRFAAS, Link, LinkTopology, PrfaasSimulator,
                        SimConfig, SystemConfig, ThroughputModel, Workload,
                        layerwise_release, paper_h20_profile,
                        paper_h200_profile, split_even, star_pairs)


def run_link(link, seconds, dt=0.01):
    steps = int(seconds / dt)
    for i in range(steps):
        link.tick(i * dt, dt)
    return steps * dt


class TestLink:
    def test_single_flow_takes_expected_time(self):
        link = Link(8e9)                       # 1 GB/s
        done = []
        link.submit(2e9, 0.0, on_done=lambda t: done.append(t))
        run_link(link, 3.0)
        assert done and abs(done[0] - 2.0) < 0.05

    def test_fair_share_two_flows(self):
        link = Link(8e9)
        done = []
        link.submit(1e9, 0.0, on_done=lambda t: done.append(("a", t)))
        link.submit(1e9, 0.0, on_done=lambda t: done.append(("b", t)))
        run_link(link, 3.0)
        # both share -> each finishes ~2s (processor sharing)
        assert len(done) == 2
        assert all(abs(t - 2.0) < 0.1 for _, t in done)

    def test_conservation(self):
        """Property: bytes sent can never exceed capacity x time."""
        link = Link(8e9, fluctuation=0.0)
        for i in range(5):
            link.submit(5e8, 0.0)
        elapsed = run_link(link, 1.5)
        assert link.sent_bytes <= 1e9 * elapsed * 1.001

    def test_layerwise_release_overlaps_compute(self):
        """With pipelining the transfer tail beyond prefill is ~bytes/bw -
        overlapped portion, vs full serialization without it."""
        link = Link(8e9)
        done = []
        rel = layerwise_release(0.0, 2.0, 1e9, n_layers=10)
        link.submit(1e9, 0.0, release=rel, on_done=lambda t: done.append(t))
        run_link(link, 4.0)
        # 1 GB at 1 GB/s with 2s compute: finishes ~max(2.0+tail, 1.0)
        assert done and 2.0 <= done[0] < 2.5

    def test_congestion_signal(self):
        link = Link(1e9)                       # tiny link
        for _ in range(10):
            link.submit(1e9, 0.0)
        run_link(link, 1.0)
        sig = link.congestion_signal()
        assert sig["util"] > 0.5 and sig["queue_bytes"] > 0

    @settings(max_examples=20, deadline=None)
    @given(st.floats(0.05, 0.4), st.integers(0, 100))
    def test_fluctuating_capacity_bounded(self, fluct, seed):
        link = Link(8e9, fluctuation=fluct, seed=seed)
        for i in range(200):
            link.tick(i * 0.05, 0.05)
            assert 0.2 <= link._mult <= 1.6


@pytest.fixture(scope="module")
def table6_setup():
    w = Workload()
    tm = ThroughputModel(paper_h200_profile(), paper_h20_profile(), w)
    sc, rate, _ = tm.grid_search(4, 8, 100e9 / 8)
    return tm, sc, rate, w


# --------------------------------------------------------------------------
# property harness: routing-decision byte charging + topology conservation
# --------------------------------------------------------------------------
_PROP_SETUP: list = []        # lazy module cache (fixtures can't mix with
                              # @given under the hypothesis fallback shim)


def _prop_setup():
    if not _PROP_SETUP:
        w = Workload()
        tm = ThroughputModel(paper_h200_profile(), paper_h20_profile(), w)
        sc, rate, _ = tm.grid_search(4, 8, 100e9 / 8)
        _PROP_SETUP.append((tm, sc, rate))
    return _PROP_SETUP[0]


class TestTopologyProperties:
    @settings(max_examples=6, deadline=None)
    @given(st.integers(1, 3), st.integers(0, 1000), st.floats(0.0, 0.5),
           st.sampled_from([1.0, 2.6073844964237387]))
    def test_bytes_sent_per_pair_equal_bytes_charged(self, k, seed, roam,
                                                     comp):
        """Every pair link's sent bytes (after draining) equal the bytes
        the routing decisions charged to that pair: prefill KV flows on
        the (PrfaaS, home) star link, cross-cache copies on the
        (cache owner, prefill target) pair — including roaming copies on
        the PD<->PD mesh.  With int8 wire compression on
        (``kv_wire_compression`` = a measured quantized/raw ratio), the
        expected bytes are recomputed here from the PROFILE directly
        (S_kv / ratio), independent of the simulator's own helpers."""
        tm, sc, rate = _prop_setup()
        w = Workload(session_prob=0.5)
        sc = SystemConfig(sc.n_prfaas, sc.n_p, sc.n_d, sc.b_out,
                          sc.threshold, kv_wire_compression=comp,
                          n_p_clusters=tuple(split_even(sc.n_p, k))
                          if k > 1 else None,
                          n_d_clusters=tuple(split_even(sc.n_d, k))
                          if k > 1 else None)
        sim = PrfaasSimulator(tm, sc, w, SimConfig(
            arrival_rate=0.4 * rate, sim_time=60.0, seed=seed,
            engine="event", pool_blocks=2_000_000, pd_clusters=k,
            pd_mesh_gbps=10.0 if k > 1 else 0.0,
            roam_prob=roam if k > 1 else 0.0))
        sim.run()
        sim.topology.run_until_idle()            # drain in-flight flows
        charged: dict = {}
        prof = tm.prfaas_profile

        def _charge(a, b, nbytes):
            key = f"{min(a, b)}|{max(a, b)}"
            charged[key] = charged.get(key, 0.0) + nbytes

        for r in sim.all_requests:
            if r.decision is None or r.prefill_start < 0:
                continue                         # never started: no flows
            if r.decision.target == PRFAAS:
                nb = prof.s_kv(r.total_len)
                if r.decision.cached_tokens:
                    nb -= prof.s_kv(r.decision.cached_tokens)
                _charge(PRFAAS, r.home, max(nb / comp, 1.0))
            if r.decision.cross_cache_transfer and r.decision.cached_tokens:
                _charge(r.decision.cache_cluster, r.decision.target,
                        max(prof.s_kv(r.decision.cached_tokens) / comp, 1.0))
        stats = sim.topology.pair_stats()
        for pair, s in stats.items():
            assert s["sent_bytes"] == pytest.approx(
                charged.get(pair, 0.0), rel=1e-6, abs=1.0), pair
        assert sim.topology.sent_bytes == pytest.approx(
            sum(charged.values()), rel=1e-6, abs=1.0)

    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 1000), st.floats(0.0, 0.4), st.integers(2, 4))
    def test_topology_conserves_backlog_across_advance(self, seed, fluct, k):
        """At every advance boundary, each pair link satisfies
        sent_bytes + live backlog == total bytes submitted to that pair
        (no creation, loss, or cross-pair migration), and full drain
        delivers exactly what was submitted."""
        rng = np.random.default_rng(seed)
        pds = [f"pd{i}" for i in range(k)]
        pairs = star_pairs(PRFAAS, pds, mesh=True)
        topo = LinkTopology.build(
            [PRFAAS] + pds, pairs,
            [float(rng.uniform(2.0, 10.0)) for _ in pairs],
            fluctuation=fluct, seed=seed)
        submitted = {f"{min(a, b)}|{max(a, b)}": 0.0 for a, b in pairs}
        for _ in range(25):
            a, b = pairs[int(rng.integers(len(pairs)))]
            nbytes = float(rng.uniform(1e6, 5e8))
            start = float(rng.uniform(0.0, 2.0))
            topo.submit(a, b, nbytes, start,
                        ramp_end=start + float(rng.uniform(0.0, 1.0)))
            submitted[f"{min(a, b)}|{max(a, b)}"] += nbytes
        t = 0.0
        for _ in range(12):
            t += float(rng.uniform(0.05, 0.8))
            topo.advance(t)
            backlogs = topo.pair_backlogs()
            for pair, s in topo.pair_stats().items():
                assert s["sent_bytes"] + backlogs[pair] == pytest.approx(
                    submitted[pair], rel=1e-9, abs=1e-3), (pair, t)
        topo.run_until_idle()
        for pair, s in topo.pair_stats().items():
            assert s["sent_bytes"] == pytest.approx(submitted[pair],
                                                    rel=1e-9, abs=1e-3)
            assert topo.pair_backlogs()[pair] == pytest.approx(0.0, abs=1e-3)


class TestSimulator:
    def test_sim_tracks_analytic_capacity(self, table6_setup):
        tm, sc, rate, w = table6_setup
        sim = PrfaasSimulator(tm, sc, w,
                              SimConfig(arrival_rate=0.85 * rate,
                                        sim_time=400, dt=0.05, seed=0))
        m = sim.run()
        # sim throughput ~= offered (below capacity) and > 70% of it
        assert m["throughput_rps"] > 0.7 * 0.85 * rate
        assert m["ttft_mean"] > 0 and m["ttft_p90"] >= m["ttft_p50"]
        assert m["offload_frac"] == pytest.approx(0.5, abs=0.12)

    def test_overload_saturates_at_capacity(self, table6_setup):
        tm, sc, rate, w = table6_setup
        sim = PrfaasSimulator(tm, sc, w,
                              SimConfig(arrival_rate=2.0 * rate,
                                        sim_time=300, dt=0.05, seed=1))
        m = sim.run()
        assert m["throughput_rps"] < 1.25 * rate     # can't exceed capacity

    def test_egress_stays_within_link(self, table6_setup):
        tm, sc, rate, w = table6_setup
        sim = PrfaasSimulator(tm, sc, w,
                              SimConfig(arrival_rate=0.9 * rate,
                                        sim_time=300, dt=0.05, seed=2,
                                        link_gbps=100.0))
        m = sim.run()
        assert m["egress_gbps"] < 100.0
        assert 5.0 < m["egress_gbps"] < 20.0          # paper: ~13 Gbps

    def test_sessions_produce_cache_hits(self, table6_setup):
        tm, sc, rate, w = table6_setup
        w2 = Workload(session_prob=0.5)
        sim = PrfaasSimulator(tm, sc, w2,
                              SimConfig(arrival_rate=0.6 * rate,
                                        sim_time=300, dt=0.05, seed=3,
                                        pool_blocks=2_000_000))
        m = sim.run()
        hit = max(c["hit_rate"] for c in m["cache"].values())
        assert hit > 0.15

    def test_congestion_triggers_threshold_adjustments(self, table6_setup):
        tm, sc, rate, w = table6_setup
        sim = PrfaasSimulator(
            tm, sc, w, SimConfig(arrival_rate=1.2 * rate, sim_time=240,
                                 dt=0.05, seed=4, link_gbps=3.0,
                                 link_fluctuation=0.2))
        m = sim.run()
        assert m["router_adjustments"] > 0            # short-term loop fired

    def test_autoscaler_converts_nodes(self, table6_setup):
        tm, _, rate, w = table6_setup
        bad = SystemConfig(4, 6, 2, 100e9 / 8, 19_400.0)   # decode-starved
        sim = PrfaasSimulator(tm, bad, w,
                              SimConfig(arrival_rate=0.8 * rate,
                                        sim_time=900, dt=0.05, seed=5,
                                        autoscale=True))
        m = sim.run()
        assert sim.autoscaler.conversions, "autoscaler never rebalanced"
        _, n_p, n_d = sim.autoscaler.conversions[-1]
        assert n_d > 2
