"""The traffic generator: the same requests and gaps for every seed, in
the seed's order, and the distributions its mix files state."""
import math
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
SEEDS = (0, 2 ** 31 + 5, 3_000_000_017)


def _gen(name, rate=1.5, window=51.0, seed=SEEDS[0]):
    mix = traffic.load_mix(TRAFFIC / f"{name}.json")
    return mix, traffic.generate(mix, rate, window, 5.0, 60.0, seed)


@pytest.mark.parametrize("name", ["mixed_offload", "short_chat"])
def test_every_seed_gets_the_same_schedule(name):
    """Every seed gets the same work: the same window requests (length
    pairs) and the same multiset of gaps, so the same lengths to warm; the
    seed orders them, and one seed always gives one timeline."""
    _, a = _gen(name)
    assert a == _gen(name)[1]
    w = [x for x in a if x.segment == "window"]
    assert all(5.0 <= x.due_s < 56.0 for x in w)
    # the outer segments reuse the window's lengths: nothing new to warm
    assert {x.prompt_len for x in a} == {x.prompt_len for x in w}
    for seed in SEEDS[1:]:
        _, b = _gen(name, seed=seed)
        v = [x for x in b if x.segment == "window"]
        assert sorted((x.prompt_len, x.n_out) for x in v) == \
            sorted((x.prompt_len, x.n_out) for x in w)
        assert [(x.prompt_len, x.n_out) for x in v] != \
            [(x.prompt_len, x.n_out) for x in w]
        assert {x.prompt_len for x in b} == {x.prompt_len for x in a}
        if name == "mixed_offload":
            # Poisson: the gaps are reordered, not drawn anew (one of the
            # multiset falls before the window's first request)
            def gaps(xs):
                return np.round(np.diff([x.due_s for x in xs]), 6)

            assert len(set(gaps(v)) & set(gaps(w))) >= len(w) - 3
            assert not np.allclose(gaps(v), gaps(w))
    _, c = _gen(name, rate=2.0)
    assert len([x for x in c if x.segment == "window"]) > len(w)


def test_prompt_tokens_depend_on_seed_only():
    _, arr = _gen("mixed_offload")
    a = traffic.prompt_tokens(7, arr, 32000)
    b = traffic.prompt_tokens(7, arr, 32000)
    c = traffic.prompt_tokens(SEEDS[1], arr, 32000)
    assert all((a[k] == b[k]).all() for k in a)
    assert any(len(a[k]) > 8 and (a[k] != c[k]).any() for k in a)
    assert all(len(a[x.rid]) == x.prompt_len for x in arr)


def test_mixed_offload_lengths_match_the_mix():
    mix, arr = _gen("mixed_offload", rate=40.0)
    w = [a for a in arr if a.segment == "window"]
    p = np.array([a.prompt_len for a in w])
    o = np.array([a.n_out for a in w])
    assert len(w) == round(40.0 * 51.0)
    assert p.min() >= 64 and p.max() <= 3584
    assert o.min() >= 16 and o.max() <= 480
    assert (p + o <= mix["max_total"]).all()
    # truncation moves the median a little off the untruncated 512 / 128
    assert 470 <= np.median(p) <= 530 and 120 <= np.median(o) <= 135
    # about a quarter are over the 1024-token offload threshold
    assert 0.18 <= (p > 1024).mean() <= 0.26


def test_truncated_lognormal_quantiles_hand_case():
    q = traffic.truncated_lognormal_quantiles(100.0, 1.0, 1e-9, 1e12, 3)
    z = [-0.967421566101701, 0.0, 0.967421566101701]   # Phi^-1(1/6, 1/2, 5/6)
    assert np.allclose(q, [100.0 * math.exp(x) for x in z], rtol=1e-9)


def test_poisson_gaps_and_rate():
    _, arr = _gen("mixed_offload", rate=20.0)
    due = np.array([a.due_s for a in arr if a.segment == "window"])
    gaps = np.diff(due)
    assert abs(len(due) - 20.0 * 51.0) <= 1
    # exponential gaps: coefficient of variation about 1
    assert 0.85 <= gaps.std() / gaps.mean() <= 1.15


def test_mmpp_arrivals_only_in_bursts():
    mix, arr = _gen("short_chat", rate=6.0)
    period = mix["arrivals"]["period_s"]
    duty = 1.0 / mix["arrivals"]["burst_factor"]
    phase = np.array([a.due_s % period for a in arr])
    assert (phase < duty * period + 1e-6).all()
    w = [a for a in arr if a.segment == "window"]
    # mean-preserving: the window offers rate * seconds requests
    assert abs(len(w) - 6.0 * 51.0) <= 0.2 * 6.0 * 51.0
