"""Scenario registry + oversubscription invariants across eviction
policies and backends.

Two concerns:

1. **Registry** (``repro.uvm.scenarios``): the built-in matrices expand to
   the advertised shapes (``oversub-full`` = 11 benchmarks × 4 ratios ×
   3 policies × 5 prefetchers), cells are stamped with their scenario and
   eviction policy (distinct resume keys per policy), and scenarios
   round-trip through JSON with every axis validated against the live
   vocabularies.

2. **Oversubscription invariants**: for every (eviction policy × backend
   × prefetcher) combination, replays satisfy the model's conservation
   laws — hits + late + faults == accesses, no evictions when memory is
   undersubscribed, eviction churn when it is not, and migrated ≥
   evicted — and the three policies genuinely produce different victim
   sequences on a thrashing trace (a guard against a policy silently
   degrading to LRU in any backend).
"""
import json

import numpy as np
import pytest

from repro.core.families import MODEL_FAMILIES
from repro.traces.trace import Trace, make_records
from repro.uvm import UVMConfig
from repro.uvm.adaptive import ADAPTIVE_POLICY
from repro.uvm.eviction import (EVICTION_POLICIES, eviction_score,
                                eviction_scores, make_eviction_policy)
from repro.uvm.golden import make_prefetcher
from repro.uvm.replay_core import ReplayRequest, dispatch, get_backend
from repro.uvm.scenarios import (DEFAULT_RATIOS, PAPER_BENCHMARKS, Scenario,
                                 available_scenarios, expand_scenario,
                                 get_scenario, register_scenario,
                                 scenario_from_dict)
from repro.uvm.sweep import PREFETCHERS, SweepCell, simulate_cell

BACKENDS = ("legacy", "numpy", "pallas")
PF_NAMES = ("none", "block", "tree", "learned", "oracle")


def _mk_trace(pages, name="scenario-synth"):
    pages = np.asarray(pages, dtype=np.int64)
    recs = make_records(len(pages))
    recs["page"] = pages
    return Trace(name, recs, {}, {}, len(pages) * 100)


def _replay(pages, pf_name, cap, eviction, backend):
    trace = _mk_trace(pages)
    config = UVMConfig(device_pages=cap, mshr_entries=16, eviction=eviction)
    req = ReplayRequest(trace, make_prefetcher(pf_name, trace, config),
                        config)
    b = get_backend(backend)
    assert b.can_replay(req), (pf_name, eviction, backend)
    return b.replay([req])[0]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_builtin_scenarios_registered():
    names = available_scenarios()
    assert "oversub-full" in names and "oversub-smoke" in names


def test_oversub_full_expands_whole_matrix():
    """The acceptance matrix: 11 paper benchmarks × ratio × policy ×
    prefetcher, every cell stamped and uniquely resumable."""
    s = get_scenario("oversub-full")
    cells = expand_scenario("oversub-full", backend="pallas")
    assert len(cells) == 11 * len(DEFAULT_RATIOS) * 3 * 5 == s.n_cells()
    assert {c.bench for c in cells} == set(PAPER_BENCHMARKS)
    assert {c.device_frac for c in cells} == set(DEFAULT_RATIOS)
    assert {c.eviction for c in cells} == set(EVICTION_POLICIES)
    assert {c.prefetcher for c in cells} == set(PREFETCHERS)
    assert all(c.scenario == "oversub-full" for c in cells)
    assert all(c.backend == "pallas" for c in cells)
    # the resume store keys every cell distinctly (policy included)
    assert len({c.key() for c in cells}) == len(cells)


def test_oversub_smoke_stays_small():
    """The CI smoke must stay sub-500k total accesses by construction:
    2 small benchmarks x 2 ratios x all policies x 2 prefetchers."""
    s = get_scenario("oversub-smoke")
    assert len(s.benches) == 2 and len(s.ratios) == 2
    assert s.evictions == EVICTION_POLICIES
    assert s.scale < 1.0
    assert s.n_cells() == 2 * 2 * 3 * 2


def test_transformer_smoke_family_axis():
    """The predictor-family CI smoke: 2 benches x adaptive eviction x
    learned, across two model families — 4 cells, each keyed distinctly
    by its family."""
    s = get_scenario("transformer-smoke")
    assert s.model_families == ("simplified", "transformer")
    assert all(f in MODEL_FAMILIES for f in s.model_families)
    assert s.evictions == (ADAPTIVE_POLICY,)
    assert s.prefetchers == ("learned",)
    assert s.n_cells() == 2 * 1 * 1 * 1 * 2
    cells = expand_scenario("transformer-smoke", backend="pallas")
    assert len(cells) == s.n_cells()
    assert {c.model_family for c in cells} == {"simplified", "transformer"}
    assert all(c.eviction == ADAPTIVE_POLICY for c in cells)
    # the family axis is part of the resume key
    assert len({c.key() for c in cells}) == len(cells)
    back = scenario_from_dict(json.loads(json.dumps(s.to_dict())))
    assert back == s and back.cells() == s.cells()


def test_scenario_json_roundtrip():
    s = get_scenario("oversub-full")
    back = scenario_from_dict(json.loads(json.dumps(s.to_dict())))
    assert back == s
    assert back.cells() == s.cells()


def test_scenario_validation_rejects_bad_axes():
    ok = dict(name="t", description="d", benches=("ATAX",), ratios=(0.5,))
    Scenario(**ok).validate()
    with pytest.raises(ValueError, match="unknown benches"):
        Scenario(**{**ok, "benches": ("NotABench",)}).validate()
    with pytest.raises(ValueError, match="unknown evictions"):
        Scenario(**{**ok, "evictions": ("lru", "mru")}).validate()
    with pytest.raises(ValueError, match="unknown prefetchers"):
        Scenario(**{**ok, "prefetchers": ("psychic",)}).validate()
    with pytest.raises(ValueError, match="unknown model_families"):
        Scenario(**{**ok, "model_families": ("lstm",)}).validate()
    with pytest.raises(ValueError, match="empty model_families"):
        Scenario(**{**ok, "model_families": ()}).validate()
    # the adaptive pseudo-policy is part of the evictions vocabulary
    Scenario(**{**ok, "evictions": ("lru", ADAPTIVE_POLICY)}).validate()
    with pytest.raises(ValueError, match="ratios"):
        Scenario(**{**ok, "ratios": ()}).validate()
    with pytest.raises(ValueError, match="ratios"):
        Scenario(**{**ok, "ratios": (0.5, -1.0)}).validate()
    with pytest.raises(ValueError, match="empty"):
        Scenario(**{**ok, "benches": ()}).validate()
    with pytest.raises(ValueError, match="scale"):
        Scenario(**{**ok, "scale": 0.0}).validate()
    with pytest.raises(ValueError, match="bad scenario name"):
        Scenario(**{**ok, "name": "a/b"}).validate()


def test_register_refuses_silent_override():
    probe = Scenario(name="probe-dup", description="d",
                     benches=("ATAX",), ratios=(0.5,))
    register_scenario(probe)
    try:
        with pytest.raises(ValueError, match="already registered"):
            register_scenario(probe)
        register_scenario(probe, replace=True)     # explicit override ok
    finally:
        from repro.uvm import scenarios as _mod
        _mod._SCENARIOS.pop("probe-dup", None)
    with pytest.raises(KeyError, match="unknown scenario"):
        get_scenario("never-registered")


def test_unknown_policy_fails_fast():
    with pytest.raises(ValueError, match="unknown eviction policy"):
        make_eviction_policy("mru")
    from repro.uvm import UVMSimulator
    tr = _mk_trace(np.arange(10))
    with pytest.raises(ValueError, match="unknown eviction policy"):
        UVMSimulator(UVMConfig(eviction="mru")).run(
            tr, make_prefetcher("none", tr, UVMConfig()))


def test_eviction_scorer_scalar_matches_array():
    """The random policy's reference mixer: scalar == vectorized, and the
    draws actually spread (no degenerate constant hash)."""
    pages = np.arange(0, 4096, 7, dtype=np.int64)
    for draw in (0, 1, 12345, 2**31 - 1):
        vec = eviction_scores(pages, draw)
        assert vec.dtype == np.uint32
        assert [eviction_score(int(p), draw) for p in pages[:32]] == \
            list(int(v) for v in vec[:32])
        # distinct draws re-rank: same pages, different priorities
        assert len(np.unique(vec)) > len(pages) * 0.99
    assert not np.array_equal(eviction_scores(pages, 0),
                              eviction_scores(pages, 1))


# ---------------------------------------------------------------------------
# oversubscription invariants, per (policy x backend)
# ---------------------------------------------------------------------------

_THRASH = np.tile(np.arange(500, dtype=np.int64), 4)     # ws ~2.8x cap


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("eviction", EVICTION_POLICIES)
def test_oversubscription_invariants(eviction, backend):
    """Conservation laws hold for every (policy, backend, prefetcher):
    access classes partition the trace, migrations bound evictions, and
    capacity pressure actually causes churn."""
    for pf_name in PF_NAMES:
        st = _replay(_THRASH, pf_name, 180, eviction, backend)
        assert st.eviction == eviction and st.backend == backend
        assert st.hits + st.late + st.faults == st.n_accesses
        assert st.pages_migrated >= st.faults
        assert st.pages_migrated - st.pages_evicted >= 0
        assert st.prefetch_used <= st.prefetch_issued
        assert st.pages_evicted > 0, (
            f"{pf_name}/{eviction}/{backend}: thrashing trace must evict")
        assert 0.0 <= st.hit_rate <= 1.0 and st.cycles > 0.0


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("eviction", EVICTION_POLICIES)
def test_undersubscribed_never_evicts(eviction, backend):
    """evictions == 0 whenever memory is undersubscribed: uncapped, or
    capacity comfortably above the working set — for every policy."""
    for cap in (None, 4096):
        st = _replay(_THRASH, "tree", cap, eviction, backend)
        assert st.pages_evicted == 0
        assert st.hits + st.late + st.faults == st.n_accesses


def _hot_cold_mix():
    """A hot 100-page set touched 3x per round, interleaved with a cold
    200-page streaming sweep per round — under a 180-page cap, LRU's
    recency order evicts the hot set every round (the streaming pages are
    newer), while access-frequency replacement keeps it resident.  The
    trace where the three policies must tell apart."""
    hot = np.repeat(np.arange(100, dtype=np.int64), 3)
    parts = []
    for k in range(12):
        parts.append(hot)
        parts.append(np.arange(1000 + 200 * k, 1000 + 200 * (k + 1),
                               dtype=np.int64))
    return np.concatenate(parts)


@pytest.mark.parametrize("backend", BACKENDS)
def test_policies_diverge_under_pressure(backend):
    """The policies must be genuinely different victim orders, not three
    names for LRU: on the hot/cold mix each policy produces a distinct
    stat vector, LRU thrashes the hot set to zero hits, random keeps a
    random subset of it, and hot/cold replacement keeps nearly all of it
    (the access-pattern-aware win of arXiv 2204.02974)."""
    by_policy = {
        pol: _replay(_hot_cold_mix(), "none", 180, pol, backend)
        for pol in EVICTION_POLICIES
    }
    sigs = {pol: (st.hits, st.late, st.faults, st.pages_evicted, st.cycles)
            for pol, st in by_policy.items()}
    assert len(set(sigs.values())) == 3, f"policies degenerate: {sigs}"
    assert by_policy["lru"].hits == 0
    assert by_policy["random"].hits > 0
    assert by_policy["hotcold"].hits > by_policy["random"].hits
    assert by_policy["hotcold"].cycles < by_policy["lru"].cycles


def test_policy_cells_have_distinct_sweep_keys():
    base = dict(bench="ATAX", prefetcher="none", scale=0.25,
                device_frac=0.5)
    keys = {SweepCell(eviction=ev, **base).key()
            for ev in EVICTION_POLICIES}
    assert len(keys) == 3


def test_simulate_cell_rows_carry_policy_columns():
    row = simulate_cell(SweepCell("ATAX", "none", scale=0.25,
                                  device_frac=0.5, eviction="random",
                                  scenario="probe"))
    assert row["eviction"] == "random"
    assert row["scenario"] == "probe"
    assert row["pages_evicted"] > 0
    lru = simulate_cell(SweepCell("ATAX", "none", scale=0.25,
                                  device_frac=0.5))
    assert lru["eviction"] == "lru"
    assert (row["hits"], row["cycles"]) != (lru["hits"], lru["cycles"])


# ---------------------------------------------------------------------------
# hypothesis widening (skipped when hypothesis is absent; CI installs it)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st_

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - degraded environment
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(st_.lists(st_.integers(0, 700), min_size=10, max_size=250),
           st_.sampled_from(EVICTION_POLICIES),
           st_.sampled_from([None, 40, 160]),
           st_.sampled_from(PF_NAMES))
    def test_invariants_random_cells(pages, eviction, cap, pf_name):
        """Random traces: the conservation laws hold for every policy on
        the numpy engine (strict_checks asserts the internal ones too)."""
        tr = _mk_trace(np.asarray(pages, dtype=np.int64))
        config = UVMConfig(device_pages=cap, mshr_entries=16,
                           eviction=eviction)
        st = dispatch(ReplayRequest(tr, make_prefetcher(pf_name, tr, config),
                                    config, strict_checks=True),
                      backend="numpy")
        assert st.hits + st.late + st.faults == st.n_accesses
        assert st.pages_migrated >= st.faults
        assert st.pages_migrated - st.pages_evicted >= 0
        assert st.prefetch_used <= st.prefetch_issued
        if cap is None:
            assert st.pages_evicted == 0
