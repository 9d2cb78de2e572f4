"""Throughput model (Eqs. 1-8), router, autoscaler, workload moments."""
import math

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (PD, PRFAAS, Autoscaler, Router, RouterConfig,
                        StageTelemetry, SystemConfig, ThroughputModel,
                        Workload, kv_throughput, paper_h20_profile,
                        paper_h200_profile)
from repro.core.workload import LogNormalLengths


@pytest.fixture(scope="module")
def tm():
    return ThroughputModel(paper_h200_profile(), paper_h20_profile(),
                           Workload())


class TestWorkloadMoments:
    def test_mean_matches_paper(self):
        w = LogNormalLengths()
        assert 26_000 < w.mean() < 28_500          # paper: ~27K

    def test_moments_match_monte_carlo(self):
        w = LogNormalLengths()
        x = w.sample(np.random.default_rng(0), 400_000)
        for t in (2000.0, 19_400.0, 60_000.0):
            assert abs(w.p_gt(t) - (x > t).mean()) < 0.01
            assert abs(w.mean_above(t) / x[x > t].mean() - 1) < 0.03
            assert abs(w.mean_below(t) / x[x <= t].mean() - 1) < 0.03

    @settings(max_examples=60, deadline=None)
    @given(st.floats(200, 120_000), st.floats(200, 120_000))
    def test_p_gt_monotone(self, a, b):
        w = LogNormalLengths()
        lo, hi = min(a, b), max(a, b)
        assert w.p_gt(lo) >= w.p_gt(hi) - 1e-12

    @settings(max_examples=60, deadline=None)
    @given(st.floats(300, 100_000))
    def test_law_of_total_expectation(self, t):
        w = LogNormalLengths()
        p = w.p_gt(t)
        total = p * w.mean_above(t) + (1 - p) * w.mean_below(t)
        assert abs(total / w.mean() - 1) < 1e-6


class TestThroughputModel:
    def test_reproduces_paper_table6(self, tm):
        """The faithful-reproduction gate: Table 6 within a few %."""
        sc, rate, _ = tm.grid_search(4, 8, 100e9 / 8)
        assert (sc.n_prfaas, sc.n_p, sc.n_d) == (4, 3, 5)
        assert abs(sc.threshold - 19_400) / 19_400 < 0.05
        assert abs(rate - 3.24) / 3.24 < 0.03
        hom = ThroughputModel(None, paper_h20_profile(), Workload())
        sc_h, rate_h, _ = hom.grid_search(0, 12, 0)
        assert (sc_h.n_p, sc_h.n_d) == (9, 3)
        assert abs(rate_h - 2.11) / 2.11 < 0.03
        naive = SystemConfig(4, 0, 8, 100e9 / 8, 0.0)
        rate_n = tm.lambda_max(naive)
        assert abs(rate_n - 2.45) / 2.45 < 0.03
        assert 1.45 < rate / rate_h < 1.62          # paper: 1.54x
        assert 1.10 < rate_n / rate_h < 1.25        # paper: 1.16x

    def test_egress_within_link(self, tm):
        sc, rate, _ = tm.grid_search(4, 8, 100e9 / 8)
        gbps = tm.egress_load(sc) * 8 / 1e9
        assert 10 < gbps < 16                        # paper: ~13 Gbps
        assert gbps < 100                            # within the link

    def test_eq7_balance_at_optimum(self, tm):
        sc, _, _ = tm.grid_search(4, 8, 100e9 / 8)
        eq7, _ = tm.balance_residuals(sc)
        p = tm.workload.lengths.p_gt(sc.threshold)
        rel = abs(eq7) / (tm.theta_prfaas(sc) / p)
        assert rel < 0.1                              # stages co-saturate

    def test_bandwidth_clips_prfaas(self, tm):
        """Eq. 3: shrinking B_out must eventually bind Θ_prfaas."""
        sc = SystemConfig(4, 3, 5, 1e9 / 8, 19_400.0)   # 1 Gbps
        sc_big = SystemConfig(4, 3, 5, 1e12, 19_400.0)
        assert tm.theta_prfaas(sc) < tm.theta_prfaas(sc_big)

    @settings(max_examples=40, deadline=None)
    @given(st.floats(1000, 100_000), st.integers(1, 7))
    def test_lambda_bounded_by_decode(self, t, n_p):
        tm_l = ThroughputModel(paper_h200_profile(), paper_h20_profile(),
                               Workload())
        sc = SystemConfig(4, n_p, 8 - n_p, 100e9 / 8, t)
        assert tm_l.lambda_max(sc) <= tm_l.theta_pdd(sc) + 1e-9

    def test_kv_wire_compression_lifts_bandwidth_bound(self, tm):
        """Beyond-paper: int8 wire KV doubles the egress ceiling; only
        matters when Θ_prfaas is bandwidth-clipped."""
        _, lam_plain, _ = tm.grid_search(8, 8, 10e9 / 8)
        _, lam_comp, _ = tm.grid_search(8, 8, 10e9 / 8,
                                        kv_wire_compression=2.0)
        assert lam_comp > lam_plain * 1.2
        # compute-bound regime (paper's 100 Gbps): no change
        _, a, _ = tm.grid_search(4, 8, 100e9 / 8)
        _, b, _ = tm.grid_search(4, 8, 100e9 / 8, kv_wire_compression=2.0)
        assert b == pytest.approx(a, rel=1e-6)

    def test_kv_throughput_drops_with_length(self):
        """§3.4.2: T_prefill grows faster than S_kv -> Φ_kv falls (hybrid)."""
        prof = paper_h200_profile()
        assert kv_throughput(prof, 131072) < kv_throughput(prof, 8192)


class TestRouter:
    def make(self, tm, t=19_400.0):
        sc = SystemConfig(4, 3, 5, 100e9 / 8, t)
        return Router(tm, sc, RouterConfig())

    def test_threshold_routing(self, tm):
        r = self.make(tm)
        assert r.route(40_000, {PD: 0, PRFAAS: 0}).target == PRFAAS
        assert r.route(5_000, {PD: 0, PRFAAS: 0}).target == PD

    def test_cache_aware_scarce(self, tm):
        """Bandwidth scarce: clusters evaluated independently."""
        r = self.make(tm)
        sig = {"util": 0.95}
        # long request whose PD-side cache makes it short locally
        d = r.route(40_000, {PD: 30_000, PRFAAS: 0}, sig)
        assert d.target == PD and d.cached_tokens == 30_000
        assert not d.cross_cache_transfer

    def test_cache_aware_abundant_cross_transfer(self, tm):
        """Bandwidth abundant: best cache anywhere + cross-cluster copy."""
        r = self.make(tm)
        sig = {"util": 0.05}
        d = r.route(40_000, {PD: 0, PRFAAS: 36_000}, sig)
        assert d.target == PD                 # incr 4K <= t
        assert d.cache_cluster == PRFAAS and d.cross_cache_transfer

    def test_congestion_raises_threshold(self, tm):
        r = self.make(tm)
        t0 = r.threshold
        r.observe_congestion({"util": 0.99, "queue_bytes": 5e9})
        assert r.threshold > t0
        for _ in range(50):
            r.observe_congestion({"util": 0.1, "queue_bytes": 0.0})
        assert r.threshold == pytest.approx(t0, rel=0.05)

    @settings(max_examples=60, deadline=None)
    @given(st.integers(128, 131072), st.integers(0, 131072),
           st.integers(0, 131072), st.floats(0, 1))
    def test_incremental_nonnegative(self, total, mpd, mprfaas, util):
        tm_l = ThroughputModel(paper_h200_profile(), paper_h20_profile(),
                               Workload())
        r = self.make(tm_l)
        d = r.route(total, {PD: min(mpd, total), PRFAAS: min(mprfaas, total)},
                    {"util": util})
        assert d.incremental >= 0
        assert d.cached_tokens + d.incremental >= total


class TestAutoscaler:
    def test_converts_roles_on_imbalance(self, tm):
        sc = SystemConfig(4, 6, 2, 100e9 / 8, 19_400.0)  # decode-starved
        r = Router(tm, sc)
        a = Autoscaler(tm, r, sc)
        a._last_eval = -1e9
        new = a.maybe_rebalance(1000.0, StageTelemetry(prefill_queue=0,
                                                       decode_queue=50))
        assert new is not None and new.n_d == 3 and new.n_p == 5

    def test_respects_period(self, tm):
        sc = SystemConfig(4, 6, 2, 100e9 / 8, 19_400.0)
        r = Router(tm, sc)
        a = Autoscaler(tm, r, sc)
        a._last_eval = 900.0
        assert a.maybe_rebalance(1000.0, StageTelemetry(0, 50)) is None

    def test_cache_hits_boost_producer_over_window(self, tm):
        """Session-aware loop: a hot prefix cache means cached tokens cost
        no prefill compute, so the effective producer rate rises and a
        P->D conversion fires where raw rates alone would not.  The hit
        fraction is windowed per evaluation (cumulative counter diffs),
        not a lifetime average."""
        sc = SystemConfig(4, 2, 6, 100e9 / 8, 19_400.0)
        # raw producer (~2.7) << consumer*1.25 (~5.9): no conversion cold
        r = Router(tm, sc)
        a = Autoscaler(tm, r, sc)
        a._last_eval = -1e9
        cold = StageTelemetry(prefill_queue=0, decode_queue=50,
                              cached_tokens=0, routed_tokens=10_000)
        assert a.maybe_rebalance(1000.0, cold) is None
        # window 2: lifetime frac is only 0.3 (4.5K/15K) but the LAST
        # window is 90% cached (4.5K of 5K) -> producer/0.1 -> P -> D
        hot = StageTelemetry(prefill_queue=0, decode_queue=50,
                             cached_tokens=4_500, routed_tokens=15_000)
        new = a.maybe_rebalance(2000.0, hot)
        assert new is not None and new.n_p == 1 and new.n_d == 7

    def test_lifetime_frac_alone_would_not_convert(self, tm):
        """Control for the window test: the same cumulative counters fed
        as a single lifetime observation (0.3 hit frac) stay balanced."""
        sc = SystemConfig(4, 2, 6, 100e9 / 8, 19_400.0)
        r = Router(tm, sc)
        a = Autoscaler(tm, r, sc)
        a._last_eval = -1e9
        tel = StageTelemetry(prefill_queue=0, decode_queue=50,
                             cache_hit_frac=0.3)
        assert a.maybe_rebalance(1000.0, tel) is None
