"""Golden-equivalence harness for the UVM replay backends.

Three guarantees, pinned by recorded fixtures (tests/golden/uvm_golden.json):

1. the legacy per-access ``UVMSimulator`` still produces the recorded stats
   (no unintentional timing-model drift),
2. the NumPy backend (``VectorizedUVMSimulator``) reproduces the legacy
   engine *exactly* on every integer counter and to 1e-6 relative on the
   float accumulators (bit-equal in practice) for every
   (trace × prefetcher) cell, and
3. the jax_pallas multi-lane backend reproduces the legacy engine for
   EVERY golden cell — all five paper-facing prefetcher families
   (none/block/tree/learned/oracle, plus the cached-prediction learned
   variant) — integer counters exact, cycles/pcie_bytes within 1e-6
   relative (bit-equal in practice), with each lane family's cells
   replayed in one lane batch (interpret mode on CPU, so CI covers it
   without a GPU).  A family whose eligibility silently shrinks to zero
   cells fails the suite (``test_pallas_eligibility_is_not_vacuous``).

Regenerate fixtures after an intentional model change with
``PYTHONPATH=src python scripts/regen_uvm_golden.py``.
"""
import json
import os

import numpy as np
import pytest

from repro.traces.trace import ROOT_PAGES, Trace, make_records
from repro.uvm import UVMConfig, UVMSimulator, VectorizedUVMSimulator
from repro.uvm.engine import MAX_SPAN_PAGES
from repro.uvm.eviction import EVICTION_POLICIES
from repro.uvm.golden import (FLOAT_FIELDS, INT_FIELDS, golden_cell,
                              golden_cell_ids, golden_cell_policy,
                              stats_to_dict)
from repro.uvm.prefetchers import Prefetcher, TreePrefetcher
from repro.uvm.replay_core import ReplayRequest, get_backend

FIXTURE = os.path.join(os.path.dirname(__file__), "golden", "uvm_golden.json")

if not os.path.exists(FIXTURE):
    pytest.fail(
        f"golden fixture missing: {FIXTURE}; regenerate with "
        "PYTHONPATH=src python scripts/regen_uvm_golden.py",
        pytrace=False)
with open(FIXTURE) as _f:
    GOLDEN = json.load(_f)["cells"]

_legacy_cache = {}


def _legacy_stats(cell_id):
    """Legacy run per cell, shared between the fixture- and the
    equivalence-assertions (the reference engine is the slow one)."""
    if cell_id not in _legacy_cache:
        trace, config, factory = golden_cell(cell_id)
        _legacy_cache[cell_id] = UVMSimulator(config).run(trace, factory())
    return _legacy_cache[cell_id]


def _assert_stats_match(got, want, *, rel, context):
    for f in INT_FIELDS:
        assert got[f] == want[f], f"{context}: {f} {got[f]} != {want[f]}"
    for f in FLOAT_FIELDS:
        assert got[f] == pytest.approx(want[f], rel=rel, abs=1e-9), (
            f"{context}: {f} {got[f]} != {want[f]}")
    # multi-tenant cells additionally pin the per-tenant accounting
    # (exact: integer counters per tenant)
    for f in ("tenant_hits", "tenant_accesses"):
        assert list(got.get(f) or []) == list(want.get(f) or []), (
            f"{context}: {f} {got.get(f)} != {want.get(f)}")


@pytest.mark.parametrize("cell_id", golden_cell_ids())
def test_legacy_matches_fixture(cell_id):
    assert cell_id in GOLDEN, (
        f"no fixture for {cell_id}; regenerate with "
        "PYTHONPATH=src python scripts/regen_uvm_golden.py")
    got = stats_to_dict(_legacy_stats(cell_id))
    _assert_stats_match(got, GOLDEN[cell_id], rel=1e-9,
                        context=f"legacy vs fixture [{cell_id}]")


@pytest.mark.parametrize("cell_id", golden_cell_ids())
def test_vectorized_matches_legacy(cell_id):
    trace, config, factory = golden_cell(cell_id)
    legacy = stats_to_dict(_legacy_stats(cell_id))
    stats = VectorizedUVMSimulator(config, strict_checks=True).run(
        trace, factory())
    # the comparison is only meaningful if the numpy engine actually ran
    # (a silent legacy fallback would match trivially)
    assert stats.backend == "numpy"
    _assert_stats_match(stats_to_dict(stats), legacy, rel=1e-6,
                        context=f"vectorized vs legacy [{cell_id}]")


def test_fixture_has_no_stale_cells():
    assert set(GOLDEN) == set(golden_cell_ids())


# ---------------------------------------------------------------------------
# pallas multi-lane backend: every golden cell of each (lane family,
# eviction policy) bucket in ONE lane batch (demand = none/block, tree,
# learned (+cached), oracle), so each group runs a single-policy program
# ---------------------------------------------------------------------------

def _family_of(cell_id):
    pf = cell_id.split("/")[1]
    return {"none": "demand", "block": "demand", "tree": "tree",
            "learned": "learned", "learned-cached": "learned",
            "learned-tf": "learned", "oracle": "oracle"}[pf]


PALLAS_LANE_GROUPS = {}
for _cell_id in golden_cell_ids():
    PALLAS_LANE_GROUPS.setdefault(
        (_family_of(_cell_id), golden_cell_policy(_cell_id)),
        []).append(_cell_id)


def test_pallas_eligibility_is_not_vacuous():
    """Empty-eligibility regression guard: every lane family AND every
    eviction policy must have golden cells the pallas backend accepts, so
    the per-(family, policy) equivalence batches below can never silently
    replay zero cells (which would let the golden guarantee pass
    vacuously)."""
    from repro.uvm.backends.pallas_backend import lane_family

    backend = get_backend("pallas")
    seen_families = set()
    seen_policies = set()
    for (family, policy), cells in PALLAS_LANE_GROUPS.items():
        assert cells, f"no golden cells for lane bucket {(family, policy)}"
        for cell_id in cells:
            trace, config, factory = golden_cell(cell_id)
            req = ReplayRequest(trace, factory(), config)
            assert backend.can_replay(req), (
                f"pallas backend declines golden cell {cell_id}: the "
                f"{(family, policy)} lane batch would silently shrink")
            seen_families.add(lane_family(req.prefetcher).split("/")[0])
            seen_policies.add(policy)
    # all five paper-facing prefetchers map onto these four kernel
    # families and every eviction policy must have in-kernel coverage —
    # no policy's lane eligibility may silently shrink to zero
    assert seen_families == {"demand", "tree", "learned", "oracle"}
    assert seen_policies == set(EVICTION_POLICIES)
    assert sum(len(c) for c in PALLAS_LANE_GROUPS.values()) == len(
        golden_cell_ids())


@pytest.mark.parametrize("group", sorted(PALLAS_LANE_GROUPS),
                         ids=lambda g: f"{g[0]}-{g[1]}")
def test_pallas_lane_batch_matches_legacy(group):
    """All golden cells of one (lane family, eviction policy) bucket —
    including the oversubscribed eviction-churn traces, the MSHR-pressure
    storm, tree escalation churn, and cached learned predictions —
    replayed as ONE multi-lane pallas batch: integer counters exact,
    floats to 1e-6 (bit-equal in practice)."""
    cells = PALLAS_LANE_GROUPS[group]
    assert cells, f"vacuous lane batch for bucket {group!r}"
    backend = get_backend("pallas")
    requests = []
    for cell_id in cells:
        trace, config, factory = golden_cell(cell_id)
        requests.append(ReplayRequest(trace, factory(), config))
    assert all(backend.can_replay(r) for r in requests)
    assert len(backend.pack_lanes(requests)) == 1, \
        f"{group} golden cells must pack into a single lane batch"
    all_stats = backend.replay(requests)
    assert len(all_stats) == len(cells)
    for cell_id, stats in zip(cells, all_stats):
        assert stats.backend == "pallas"
        assert stats.eviction == group[1]
        _assert_stats_match(stats_to_dict(stats),
                            stats_to_dict(_legacy_stats(cell_id)), rel=1e-6,
                            context=f"pallas vs legacy [{cell_id}]")


def test_cached_learned_matches_plain_learned():
    """The predcache round trip is invisible to the replay: every
    learned-cached fixture is identical to its plain learned sibling."""
    pairs = [c for c in GOLDEN if c.endswith("/learned-cached")]
    assert pairs
    for cell_id in pairs:
        plain = cell_id.replace("/learned-cached", "/learned")
        assert GOLDEN[cell_id] == GOLDEN[plain], cell_id


def test_family_keyed_cache_distinguishes_learned_tf():
    """learned-tf rides the same predcache round trip as learned-cached
    but under ``model_family="transformer"`` with a different prediction
    distance.  If the cache key ignored the model family, the round trip
    would cross-serve the simplified cells' distance-32 array and every
    learned-tf fixture would collapse onto its plain learned sibling."""
    pairs = [c for c in GOLDEN if c.endswith("/learned-tf")]
    assert pairs
    assert any(GOLDEN[c] != GOLDEN[c.replace("/learned-tf", "/learned")]
               for c in pairs)


def test_timeline_equivalence():
    """The optional (cycle, bytes) transfer timeline matches event-for-event."""
    cell_id = "bicg-cluster/tree"
    trace, config, factory = golden_cell(cell_id)
    t_legacy = UVMSimulator(config, record_timeline=True).run(
        trace, factory()).timeline
    t_vec = VectorizedUVMSimulator(config, record_timeline=True).run(
        trace, factory()).timeline
    assert t_legacy.shape == t_vec.shape
    np.testing.assert_allclose(t_vec, t_legacy, rtol=1e-9)


# ---------------------------------------------------------------------------
# engine fallbacks
# ---------------------------------------------------------------------------

def _mk_trace(pages, name="synth"):
    pages = np.asarray(pages, dtype=np.int64)
    recs = make_records(len(pages))
    recs["page"] = pages
    return Trace(name, recs, {}, {}, len(pages) * 100)


class _EveryOtherPrefetcher(Prefetcher):
    """Unknown subclass: must route to the legacy engine (it may emit pages
    outside the vectorized engine's dense page span)."""

    name = "every-other"

    def on_fault(self, index, page, resident):
        return [page + 1] if (page + 1) not in resident else []

    def on_access(self, index, page, resident, clock=0.0):
        q = page + 2
        if index % 2 == 0 and q not in resident:
            return [q]
        return []


def test_generic_prefetcher_fallback_is_exact():
    tr = _mk_trace(np.tile(np.arange(200), 4))
    s1 = stats_to_dict(UVMSimulator().run(tr, _EveryOtherPrefetcher()))
    s2 = stats_to_dict(
        VectorizedUVMSimulator(strict_checks=True).run(
            tr, _EveryOtherPrefetcher()))
    _assert_stats_match(s2, s1, rel=1e-9, context="generic fallback")


def test_huge_span_falls_back_to_legacy():
    pages = np.array([0, MAX_SPAN_PAGES * 2, 0, 7], dtype=np.int64)
    tr = _mk_trace(pages)
    s1 = stats_to_dict(UVMSimulator().run(tr, TreePrefetcher()))
    s2 = stats_to_dict(VectorizedUVMSimulator().run(tr, TreePrefetcher()))
    _assert_stats_match(s2, s1, rel=1e-9, context="span fallback")


def test_empty_trace():
    tr = _mk_trace(np.empty(0, dtype=np.int64))
    st = VectorizedUVMSimulator().run(tr, TreePrefetcher())
    assert st.n_accesses == 0 and st.cycles == 0.0 and st.faults == 0


# ---------------------------------------------------------------------------
# invariants (strict_checks also asserts monotone clock and
# never-evict-in-flight inside the engine)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell_id", golden_cell_ids())
def test_invariants(cell_id):
    trace, config, factory = golden_cell(cell_id)
    st = VectorizedUVMSimulator(config, strict_checks=True).run(
        trace, factory())
    assert st.hits + st.late + st.faults == st.n_accesses
    assert 0.0 <= st.accuracy <= 1.0
    assert 0.0 <= st.coverage <= 1.0
    assert 0.0 <= st.hit_rate <= 1.0
    assert 0.0 <= st.unity <= 1.0
    assert st.prefetch_used <= st.prefetch_issued
    assert st.pages_migrated >= st.faults
    assert st.cycles >= 0.0
    if config.device_pages is not None:
        assert st.pages_migrated - st.pages_evicted >= 0


# ---------------------------------------------------------------------------
# property-based equivalence (skipped when hypothesis is absent)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st_

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - degraded environment
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(st_.lists(
        st_.one_of(
            st_.tuples(st_.just("migrate"),
                       st_.lists(st_.integers(0, 2 * ROOT_PAGES - 1),
                                 min_size=1, max_size=40, unique=True)),
            st_.tuples(st_.just("evict"),
                       st_.integers(0, 2 * ROOT_PAGES - 1)),
            st_.tuples(st_.just("fault"),
                       st_.integers(0, 2 * ROOT_PAGES - 1)),
        ), min_size=1, max_size=80))
    def test_tree_adapter_matches_dict_counts(ops):
        """Vectorized per-level count arrays vs the legacy dict on random
        migrate/evict/fault streams: node occupancy and the on_fault extras
        (pages AND order) must agree after every operation."""
        from repro.uvm.engine import _TreeAdapter

        span = 2 * ROOT_PAGES
        arrival = np.full(span, np.inf)
        resident = set()
        legacy = TreePrefetcher()
        adapter = _TreeAdapter(TreePrefetcher(), arrival, 0)

        def _migrate(pages):
            for q in pages:
                resident.add(q)
                arrival[q] = 0.0
            legacy.on_migrate(list(pages))
            adapter.on_migrate(list(pages))

        for op in ops:
            if op[0] == "migrate":
                fresh = [q for q in op[1] if q not in resident]
                if fresh:
                    _migrate(fresh)
            elif op[0] == "evict":
                q = op[1]
                if q in resident:
                    resident.discard(q)
                    arrival[q] = np.inf
                    legacy.on_evict(q)
                    adapter.on_evict(q)
            else:                        # fault, replaying engine order:
                q = op[1]                # insert + migrate, then on_fault
                if q in resident:
                    continue
                _migrate([q])
                want = legacy.on_fault(0, q, resident)
                got = adapter.on_fault(0, q, resident)
                assert [int(x) for x in got] == want
                if want:
                    _migrate(want)       # the engine schedules the extras
            for lv in range(TreePrefetcher.LEVELS + 1):
                nz = {i: int(c) for i, c in enumerate(adapter.counts[lv])
                      if c}
                dic = {node: int(c)
                       for (level, node), c in legacy.counts.items()
                       if level == lv and c}
                assert nz == dic, f"level {lv} counts diverged"

    @settings(max_examples=25, deadline=None)
    @given(st_.lists(st_.integers(0, 600), min_size=20, max_size=300),
           st_.sampled_from(["none", "block", "tree", "learned",
                             "learned-cached", "learned-tf", "oracle"]),
           st_.sampled_from([None, 48, 200]))
    def test_property_equivalence(pages, pf_name, cap):
        from repro.uvm.golden import make_prefetcher

        tr = _mk_trace(np.asarray(pages, dtype=np.int64))
        config = UVMConfig(device_pages=cap, mshr_entries=16)
        s1 = stats_to_dict(
            UVMSimulator(config).run(
                tr, make_prefetcher(pf_name, tr, config)))
        s2 = stats_to_dict(
            VectorizedUVMSimulator(config, strict_checks=True).run(
                tr, make_prefetcher(pf_name, tr, config)))
        _assert_stats_match(s2, s1, rel=1e-9,
                            context=f"property [{pf_name} cap={cap}]")
