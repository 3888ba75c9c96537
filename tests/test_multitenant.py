"""Multi-tenant replay plane: trace interleaver, capacity splits, and
the per-tenant eviction-isolation guarantee.

The acceptance bar for the tenancy axis is the *isolation property*: a
tenant whose working set fits inside its hard quota must see a
bit-identical hit count whether its co-tenant is idle or thrashing —
quota + tenant-masked victim selection make the co-tenant invisible to
its residency.  The shared-capacity control shows the interference the
quota removes (the co-tenant's churn evicts the protected tenant's
pages), so the property test cannot pass vacuously.
"""
import dataclasses

import numpy as np
import pytest

from repro.traces.interleave import (N_TENANTS, build_mt_trace, is_mt_bench,
                                     mt_component_trace, split_mt_bench,
                                     tenant_boundary, tenant_counts,
                                     tenant_last_index, tenant_stream)
from repro.traces.trace import ROOT_PAGES, Trace, make_records
from repro.uvm import UVMConfig, UVMSimulator
from repro.uvm.eviction import resolve_tenancy
from repro.uvm.prefetchers import NoPrefetcher
from repro.uvm.replay_core import ReplayRequest, dispatch
from repro.uvm.sweep import MT_FIELDS, SweepCell, parse_capacity_split, \
    simulate_cell


def _mk_mt_trace(pages0, pages1, boundary, name="mt-synth"):
    """Synthetic two-tenant trace: tenant 1's pages are rebased above
    ``boundary`` and the streams merge clock-proportionally (the same
    key arithmetic as the interleaver), so any (pages0, pages1) pair
    becomes a valid multi-tenant trace."""
    pages0 = np.asarray(pages0, dtype=np.int64)
    pages1 = np.asarray(pages1, dtype=np.int64) + boundary
    assert pages0.size and int(pages0.max()) < boundary
    na, nb = len(pages0), len(pages1)
    keys = np.concatenate([np.arange(1, na + 1, dtype=np.int64) * nb,
                           np.arange(1, nb + 1, dtype=np.int64) * na])
    order = np.argsort(keys, kind="stable")
    pages = np.concatenate([pages0, pages1])[order]
    recs = make_records(len(pages))
    recs["page"] = pages
    return Trace(name, recs, {}, {}, len(pages) * 100,
                 meta={"mt": {"benches": ["A", "B"], "tenants": N_TENANTS,
                              "boundary": int(boundary)}})


# ---------------------------------------------------------------------------
# interleaver
# ---------------------------------------------------------------------------

def test_mt_bench_name_predicate():
    assert is_mt_bench("ATAX+Pathfinder")
    assert split_mt_bench("ATAX+Pathfinder") == ("ATAX", "Pathfinder")
    for bad in ("ATAX", "ATAX+NoSuchBench", "A+B+C", "+ATAX", "ATAX+",
                "ServeDecode+ATAX", 7, None):
        assert not is_mt_bench(bad), bad


def test_build_mt_trace_is_deterministic_and_disjoint():
    t1 = build_mt_trace("ATAX+Pathfinder", scale=0.25)
    t2 = build_mt_trace("ATAX+Pathfinder", scale=0.25)
    np.testing.assert_array_equal(t1.accesses, t2.accesses)
    assert t1.meta == t2.meta

    boundary = tenant_boundary(t1)
    assert boundary is not None and boundary % ROOT_PAGES == 0
    pages = np.asarray(t1.pages)
    stream = tenant_stream(t1)
    # the boundary IS the tenancy encoding: regions are disjoint with a
    # guard root window between tenant 0's span and the boundary
    assert int(pages[stream == 0].max()) < boundary - ROOT_PAGES
    assert int(pages[stream == 1].min()) >= boundary
    # both components survive with every access
    atax = build_mt_trace("ATAX+Pathfinder", scale=0.25)
    n0, n1 = tenant_counts(atax)
    assert n0 + n1 == len(atax)
    assert n0 > 0 and n1 > 0
    # a different seed relocates the regions but keeps the counts
    t3 = build_mt_trace("ATAX+Pathfinder", scale=0.25, seed=1)
    assert tenant_boundary(t3) != boundary
    assert tenant_counts(t3) == (n0, n1)


def test_mt_merge_is_clock_proportional():
    """Accesses interleave by per-tenant progress fraction — a tenant is
    never starved to the end of the stream: after any prefix of the merged
    trace, each tenant's progress stays within one access of the
    prefix's proportional share."""
    tr = _mk_mt_trace(np.arange(300), np.arange(100), boundary=1024)
    stream = tenant_stream(tr)
    n0, n1 = tenant_counts(tr)
    done1 = np.cumsum(stream == 1)
    done0 = np.arange(1, len(stream) + 1) - done1
    frac = np.arange(1, len(stream) + 1) / len(stream)
    assert np.all(np.abs(done0 / n0 - frac) <= 1.0 / n0 + 1.0 / len(stream))
    assert np.all(np.abs(done1 / n1 - frac) <= 1.0 / n1 + 1.0 / len(stream))
    # tenant 0 wins exact progress ties
    assert stream[0] == 0 and int(tenant_last_index(tr)[1]) == \
        len(stream) - 1


def test_tenancy_views_are_derived_not_stored():
    """tenant_stream/counts/last_index stay correct on any slice because
    they recompute from pages vs. the boundary."""
    tr = _mk_mt_trace(np.arange(40), np.arange(10), boundary=512)
    half = dataclasses.replace(tr, accesses=tr.accesses[:25])
    stream = tenant_stream(half)
    assert len(stream) == 25
    n0, n1 = tenant_counts(half)
    assert n0 == int((stream == 0).sum()) and n1 == int((stream == 1).sum())
    last = tenant_last_index(half)
    for t in range(N_TENANTS):
        assert stream[last[t]] == t
    # single-tenant traces yield None everywhere
    recs = make_records(4)
    recs["page"] = np.arange(4)
    plain = Trace("plain", recs, {}, {}, 400)
    assert tenant_stream(plain) is None
    assert tenant_counts(plain) is None
    assert tenant_last_index(plain) is None
    with pytest.raises(ValueError, match="not a multi-tenant"):
        mt_component_trace(plain, 0)


def test_mt_component_trace_extracts_solo_replay():
    tr = build_mt_trace("ATAX+Pathfinder", scale=0.25)
    stream = tenant_stream(tr)
    for t in range(N_TENANTS):
        solo = mt_component_trace(tr, t)
        np.testing.assert_array_equal(
            np.asarray(solo.pages), np.asarray(tr.pages)[stream == t])
        assert tenant_stream(solo) is None       # no mt sidecar: solo
        assert solo.name.endswith(f"@t{t}")
        assert all(k.startswith(f"t{t}/") for k in solo.array_bases)
        assert solo.n_instructions > 0
    assert (mt_component_trace(tr, 0).n_instructions
            + mt_component_trace(tr, 1).n_instructions
            <= tr.n_instructions + 1)


# ---------------------------------------------------------------------------
# capacity splits + tenancy validation
# ---------------------------------------------------------------------------

def test_parse_capacity_split():
    assert parse_capacity_split(None) is None
    assert parse_capacity_split("shared") is None
    assert parse_capacity_split("0.5/0.5") == (0.5, 0.5)
    assert parse_capacity_split("0.4/0.4") == (0.4, 0.4)
    assert parse_capacity_split("0/1") == (0.0, 1.0)
    for bad in ("0.7/0.7", "-0.1/0.5", "abc", "0.5", "0.3/0.3/0.3", ""):
        with pytest.raises(ValueError):
            parse_capacity_split(bad)


def test_resolve_tenancy_validation():
    tr = _mk_mt_trace(np.arange(10), np.arange(10), boundary=512)
    assert resolve_tenancy(tr, UVMConfig()) is not None          # shared
    ten = resolve_tenancy(tr, UVMConfig(device_pages=100,
                                        tenant_pages=(40, 40)))
    assert ten.quotas == (40, 40) and ten.spill == 20
    assert ten.allowed(0, 0) == (60, 60)
    assert ten.allowed(0, 50) == (50, 60)        # t1 borrowed 10 spill
    assert ten.allowed(55, 60) == (40, 45)
    recs = make_records(4)
    recs["page"] = np.arange(4)
    plain = Trace("plain", recs, {}, {}, 400)
    assert resolve_tenancy(plain, UVMConfig()) is None
    with pytest.raises(ValueError, match="not\\s+multi-tenant"):
        resolve_tenancy(plain, UVMConfig(device_pages=100,
                                         tenant_pages=(40, 40)))
    with pytest.raises(ValueError, match="device_pages"):
        resolve_tenancy(tr, UVMConfig(tenant_pages=(40, 40)))
    with pytest.raises(ValueError, match="exceed"):
        resolve_tenancy(tr, UVMConfig(device_pages=50,
                                      tenant_pages=(40, 40)))
    with pytest.raises(ValueError, match="non-negative"):
        resolve_tenancy(tr, UVMConfig(device_pages=100,
                                      tenant_pages=(-1, 40)))


def test_mt_scenarios_registered():
    from repro.uvm.scenarios import Scenario, expand_scenario, get_scenario

    smoke = get_scenario("mt-smoke")
    assert smoke.n_cells() == 36
    cells = expand_scenario("mt-smoke")
    assert len(cells) == 36
    assert {c.bench for c in cells} == {"ATAX+Pathfinder"}
    assert {c.capacity_split for c in cells} == {"shared", "0.5/0.5",
                                                 "0.4/0.4"}
    assert get_scenario("mt-full").n_cells() > smoke.n_cells()
    # quota splits require every bench to be an interleaved pair
    with pytest.raises(ValueError, match="multi-tenant"):
        Scenario(name="bad-mt", description="x",
                 benches=("ATAX", "ATAX+Pathfinder"), ratios=(0.5,),
                 capacity_splits=("0.5/0.5",)).validate()
    with pytest.raises(ValueError, match="capacity_splits"):
        Scenario(name="bad-mt2", description="x", benches=("ATAX",),
                 ratios=(0.5,), capacity_splits=()).validate()
    with pytest.raises(ValueError, match="sum"):
        Scenario(name="bad-mt3", description="x",
                 benches=("ATAX+Pathfinder",), ratios=(0.5,),
                 capacity_splits=("0.8/0.8",)).validate()


# ---------------------------------------------------------------------------
# per-tenant eviction isolation (the tentpole acceptance property)
# ---------------------------------------------------------------------------

BOUNDARY = 2 * ROOT_PAGES                      # tenant 1 starts at 1024


def _protected_run(co_pages, tenant_pages, eviction="lru"):
    """Replay tenant 0's quota-fitting cyclic sweep against a given
    co-tenant stream; returns the full stats."""
    ws0 = 200
    pages0 = np.tile(np.arange(ws0, dtype=np.int64), 5)     # 1000 accesses
    tr = _mk_mt_trace(pages0, co_pages, boundary=BOUNDARY)
    cfg = UVMConfig(device_pages=400, tenant_pages=tenant_pages,
                    eviction=eviction)
    return dispatch(ReplayRequest(tr, NoPrefetcher(), cfg, strict_checks=True),
                    backend="numpy")


IDLE = np.arange(10, dtype=np.int64)                        # 10 accesses
THRASH = np.tile(np.arange(600, dtype=np.int64), 2)         # 1200 accesses


@pytest.mark.parametrize("eviction", ["lru", "random", "hotcold"])
def test_quota_isolates_protected_tenant(eviction):
    """Tenant 0's working set (200 pages) fits its hard quota (250 of
    400): its hit COUNT must be bit-identical (+-0) whether tenant 1
    idles over 10 pages or thrashes 600 pages through its 100-page quota
    + 50-page spill — under every eviction policy."""
    idle = _protected_run(IDLE, (250, 100), eviction)
    thrash = _protected_run(THRASH, (250, 100), eviction)
    assert idle.tenant_accesses[0] == thrash.tenant_accesses[0] == 1000
    assert idle.tenant_hits[0] == thrash.tenant_hits[0]
    # the co-tenant genuinely thrashed: it evicted pages, tenant 0's
    # stream still ran hot (first sweep faults, the rest hits)
    assert thrash.pages_evicted > 0
    assert idle.tenant_hits[0] == 1000 - 200


def test_shared_capacity_control_shows_interference():
    """Without quotas the same thrashing co-tenant evicts tenant 0's
    pages — the isolation above is the quota's doing, not an artifact of
    the traces."""
    idle = _protected_run(IDLE, None)
    thrash = _protected_run(THRASH, None)
    assert idle.tenant_hits[0] == 1000 - 200       # fits shared capacity
    assert thrash.tenant_hits[0] < idle.tenant_hits[0]


def test_isolation_property_matches_legacy_engine():
    """The quota-isolated replay is pinned across engines too: legacy and
    numpy agree on the per-tenant counters of the property trace."""
    tr = _mk_mt_trace(np.tile(np.arange(200, dtype=np.int64), 5), THRASH,
                      boundary=BOUNDARY)
    cfg = UVMConfig(device_pages=400, tenant_pages=(250, 100),
                    eviction="hotcold")
    legacy = UVMSimulator(cfg).run(tr, NoPrefetcher())
    vec = dispatch(ReplayRequest(tr, NoPrefetcher(), cfg, strict_checks=True),
                   backend="numpy")
    assert tuple(vec.tenant_hits) == tuple(legacy.tenant_hits)
    assert tuple(vec.tenant_accesses) == tuple(legacy.tenant_accesses)
    assert vec.hits == legacy.hits and vec.faults == legacy.faults
    assert vec.pages_evicted == legacy.pages_evicted


# ---------------------------------------------------------------------------
# sweep rows carry the mt columns
# ---------------------------------------------------------------------------

def test_mt_sweep_row_records_tenant_columns():
    row = simulate_cell(SweepCell("ATAX+Pathfinder", "none", scale=0.25,
                                  device_frac=0.75,
                                  capacity_split="0.5/0.5"))
    assert row["tenants"] == N_TENANTS
    assert row["capacity_split"] == "0.5/0.5"
    for f in ("hit_rate_t0", "hit_rate_t1", "slowdown_t0", "slowdown_t1",
              "interference_slowdown"):
        assert isinstance(row[f], float), f
        assert row[f] > 0.0
    assert row["interference_slowdown"] == pytest.approx(
        max(row["slowdown_t0"], row["slowdown_t1"]))
    # shared-mode mt rows record the split as "shared"
    shared = simulate_cell(SweepCell("ATAX+Pathfinder", "none", scale=0.25,
                                     device_frac=0.75))
    assert shared["capacity_split"] == "shared"
    assert shared["tenants"] == N_TENANTS
    # single-tenant rows keep the mt columns as None (schema-stable)
    plain = simulate_cell(SweepCell("ATAX", "none", scale=0.25))
    for f in MT_FIELDS:
        assert plain[f] is None, f
