"""Replay-backend layer: registry/contract, dispatch fallbacks, and the
jax_pallas multi-lane engine.

The lane-packing property test is the backend's core guarantee: a
lane-batched pallas replay of N random cells must equal N independent
NumPy replays — integer counters exact, cycles/pcie_bytes to 1e-6 —
including ragged trace lengths and oversubscribed (LRU-evicting) cells.
"""
import numpy as np
import pytest

from repro.traces.trace import ROOT_PAGES, Trace, make_records
from repro.uvm import UVMConfig
from repro.uvm.backends.pallas_backend import (MAX_LANE_SPAN_PAGES,
                                               MAX_LANES_PER_BATCH,
                                               _N_IPARAMS,
                                               PallasReplayBackend, _bucket,
                                               lane_family)
from repro.uvm.golden import make_prefetcher as golden_prefetcher
from repro.uvm.prefetchers import (BlockPrefetcher, NoPrefetcher,
                                   OraclePrefetcher, TreePrefetcher)
from repro.uvm.replay_core import (ReplayRequest, available_backends,
                                   backend_chain, dispatch, get_backend,
                                   resolve_backend)

INT_FIELDS = ("n_accesses", "hits", "late", "faults", "prefetch_issued",
              "prefetch_used", "pages_migrated", "pages_evicted")


def _mk_trace(pages, name="synth"):
    pages = np.asarray(pages, dtype=np.int64)
    recs = make_records(len(pages))
    recs["page"] = pages
    return Trace(name, recs, {}, {}, len(pages) * 100)


def _req(pages, pf=None, cap=None, mshr=64):
    config = UVMConfig(device_pages=cap, mshr_entries=mshr)
    return ReplayRequest(_mk_trace(pages), pf or NoPrefetcher(), config)


def _assert_equivalent(got, want, context=""):
    for f in INT_FIELDS:
        assert getattr(got, f) == getattr(want, f), (
            f"{context}: {f} {getattr(got, f)} != {getattr(want, f)}")
    assert got.cycles == pytest.approx(want.cycles, rel=1e-6), context
    assert got.pcie_bytes == pytest.approx(want.pcie_bytes, rel=1e-6), context


# ---------------------------------------------------------------------------
# registry + dispatch contract
# ---------------------------------------------------------------------------

def test_registry_has_builtin_backends():
    assert {"legacy", "numpy", "pallas"} <= set(available_backends())
    for name in ("legacy", "numpy", "pallas"):
        assert get_backend(name).name == name
    with pytest.raises(ValueError, match="unknown replay backend"):
        get_backend("cuda")


def test_backend_chains_end_in_legacy():
    assert backend_chain("legacy") == ["legacy"]
    assert backend_chain("numpy") == ["numpy", "legacy"]
    assert backend_chain("pallas") == ["pallas", "numpy", "legacy"]
    assert backend_chain("auto")[-1] == "legacy"
    with pytest.raises(ValueError):
        backend_chain("mps")


def test_dispatch_records_backend():
    assert dispatch(_req(np.arange(200) % 64), "numpy").backend == "numpy"
    assert dispatch(_req(np.arange(200) % 64), "pallas").backend == "pallas"
    assert dispatch(_req(np.arange(200) % 64), "legacy").backend == "legacy"


def test_unpackable_request_falls_back_visibly():
    """A cell the lanes decline (page span beyond the per-lane ceiling)
    drops down the chain to the NumPy path and says so in the stats
    instead of silently covering."""
    pages = np.array([0, MAX_LANE_SPAN_PAGES + 1, 0, 7], dtype=np.int64)
    r = _req(pages)
    assert not get_backend("pallas").can_replay(r)
    assert resolve_backend(r, "pallas").name == "numpy"
    assert dispatch(r, "pallas").backend == "numpy"


def test_every_prefetcher_family_is_packable():
    """All five paper-facing prefetcher families replay in-kernel: the
    pallas chain keeps them instead of falling back."""
    pages = np.arange(200) % 64
    tr = _mk_trace(pages)
    config = UVMConfig()
    for name in ("none", "block", "tree", "learned", "oracle"):
        r = ReplayRequest(_mk_trace(pages),
                          golden_prefetcher(name, tr, config), config)
        assert get_backend("pallas").can_replay(r), name
        assert resolve_backend(r, "pallas").name == "pallas", name
        assert dispatch(r, "pallas").backend == "pallas", name


def test_pallas_declines_timelines_and_empty_traces():
    backend = get_backend("pallas")
    assert not backend.can_replay(
        ReplayRequest(_mk_trace(np.arange(10)), NoPrefetcher(), UVMConfig(),
                      record_timeline=True))
    assert not backend.can_replay(_req(np.empty(0, dtype=np.int64)))


def test_pallas_declines_overlong_lanes():
    """Lanes longer than MAX_LANE_ACCESSES would run the int32 LRU touch
    counter out of headroom — they must fall back, not silently wrap."""
    from repro.uvm.backends.pallas_backend import MAX_LANE_ACCESSES

    backend = get_backend("pallas")
    ok = _req(np.zeros(8, dtype=np.int64))
    too_long = _req(np.zeros(8, dtype=np.int64))
    # fake the length with a zero-copy broadcast view: can_replay rejects
    # on len(trace.pages) before touching the contents
    too_long.trace.accesses = np.broadcast_to(
        too_long.trace.accesses[:1], (MAX_LANE_ACCESSES + 1,))
    assert backend.can_replay(ok)
    assert not backend.can_replay(too_long)


def test_pallas_replay_rejects_unpackable():
    backend = get_backend("pallas")
    too_wide = _req(np.array([0, MAX_LANE_SPAN_PAGES + 1], dtype=np.int64))
    with pytest.raises(ValueError, match="not packable"):
        backend.replay([too_wide])


def test_pallas_declines_oversized_oracle_lookahead():
    """The oracle scan window is a static kernel shape: absurd lookaheads
    fall back instead of bloating the kernel."""
    from repro.uvm.backends.pallas_backend import MAX_ORACLE_LOOKAHEAD

    backend = get_backend("pallas")
    pages = np.arange(100, dtype=np.int64)
    ok = _req(pages, pf=OraclePrefetcher(pages))
    too_wide = _req(pages, pf=OraclePrefetcher(
        pages, lookahead=MAX_ORACLE_LOOKAHEAD + 1))
    assert backend.can_replay(ok)
    assert not backend.can_replay(too_wide)
    assert dispatch(too_wide, "pallas").backend == "numpy"


def test_numpy_runtime_failure_propagates(monkeypatch):
    """A numpy-engine crash must surface, not silently serve legacy
    results (which would let the golden equivalence suite pass
    vacuously)."""
    from repro.uvm.backends.numpy_backend import NumpyReplayBackend

    def _boom(self, requests):
        raise IndexError("synthetic engine bug")

    monkeypatch.setattr(NumpyReplayBackend, "replay", _boom)
    with pytest.raises(IndexError, match="synthetic engine bug"):
        dispatch(ReplayRequest(_mk_trace(np.arange(10)), NoPrefetcher(),
                               UVMConfig()), backend="numpy")


def test_pallas_runtime_failure_degrades_with_warning(monkeypatch):
    """A lane batch that fails at runtime raises: the cell is never
    replayed on the NumPy engine in its place (a chip run must not
    silently become a host run), and no fallback warning is issued."""
    import warnings

    from repro.uvm.backends.pallas_backend import PallasReplayBackend

    def _boom(self, requests):
        raise RuntimeError("synthetic lowering failure")

    monkeypatch.setattr(PallasReplayBackend, "replay", _boom)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with pytest.raises(RuntimeError, match="synthetic lowering failure"):
            dispatch(_req(np.arange(50)), "pallas")


def test_is_native_consistent_with_interpret_policy(monkeypatch):
    """The lanes are native only on a TPU: on a CPU host ``auto`` resolves
    to the NumPy engine, and a sweep worker (whose parent holds the chip)
    is never native and never asks JAX."""
    import jax

    from repro.uvm import replay_core

    assert jax.default_backend() == "cpu"
    assert get_backend("pallas").is_native() is False
    assert backend_chain("auto") == ["numpy", "legacy"]

    def _no_jax():
        raise AssertionError("a worker must not query the JAX backend")

    monkeypatch.setattr(replay_core, "_device_held_by_parent", True)
    monkeypatch.setattr(jax, "default_backend", _no_jax)
    assert get_backend("pallas").is_native() is False
    assert backend_chain("auto") == ["numpy", "legacy"]


def test_worker_process_refuses_device_work(monkeypatch):
    """In a sweep worker the parent holds the chip: a lane batch or a
    predictor training run asked of it raises loudly instead of
    initialising the accelerator (or quietly using the CPU)."""
    from repro.uvm import predcache, replay_core

    monkeypatch.setattr(replay_core, "_device_held_by_parent", True)
    with pytest.raises(RuntimeError, match="parent process holds"):
        get_backend("pallas").replay([_req(np.arange(50))])
    with pytest.raises(RuntimeError, match="parent process holds"):
        dispatch(_req(np.arange(50)), "pallas")
    monkeypatch.setenv("REPRO_PREDCACHE", "0")
    with pytest.raises(RuntimeError, match="predictor training"):
        predcache.get_or_train(_mk_trace(np.arange(300) % 64), steps=1)
    # host backends are unaffected
    assert dispatch(_req(np.arange(50)), "numpy").backend == "numpy"


def test_fits_batch_budgets():
    backend = get_backend("pallas")
    assert backend.fits_batch([], ("demand", "lru", 100, 512))
    assert backend.fits_batch([("demand", "lru", 100, 512)],
                              ("demand", "lru", 100, 512))
    from repro.uvm.backends.pallas_backend import (MAX_BATCH_STATE_PAGES,
                                                   MAX_LANES_PER_BATCH)
    assert not backend.fits_batch(
        [("demand", "lru", 100, 512)] * MAX_LANES_PER_BATCH,
        ("demand", "lru", 100, 512))
    huge_span = MAX_BATCH_STATE_PAGES // 2 + 1
    assert not backend.fits_batch([("demand", "lru", 100, huge_span)],
                                  ("demand", "lru", 100, huge_span))


def test_fits_batch_never_mixes_families():
    """A lane batch is one kernel: incompatible prefetcher families must
    never share it, whatever the shape budgets say."""
    backend = get_backend("pallas")
    assert not backend.fits_batch([("demand", "lru", 100, 512)],
                                  ("tree", "lru", 100, 512))
    assert not backend.fits_batch([("tree", "lru", 100, 512)],
                                  ("learned", "lru", 100, 512))
    # different oracle lookaheads are different kernels too
    assert not backend.fits_batch([("oracle/96", "lru", 100, 512)],
                                  ("oracle/32", "lru", 100, 512))
    assert backend.fits_batch([("oracle/96", "lru", 100, 512)],
                              ("oracle/96", "lru", 100, 512))


def test_fits_batch_mixes_policies_not_families():
    """The eviction policy is a per-lane value of the lane program:
    lanes of every policy share a batch of their family, and a batch
    still never takes a lane of another family, whatever its policy."""
    backend = get_backend("pallas")
    fams = ("demand", "tree", "learned", "oracle/96")
    for fam in fams:
        assert backend.fits_batch([(fam, "lru", 100, 512)],
                                  (fam, "random", 100, 512))
        assert backend.fits_batch([(fam, "lru", 100, 512),
                                   (fam, "random", 100, 512)],
                                  (fam, "hotcold", 100, 512))
        assert backend.fits_batch([(fam, "hotcold", 100, 512)],
                                  (fam, "hotcold", 100, 512))
        for other in fams:
            if other != fam:
                assert not backend.fits_batch([(fam, "lru", 100, 512)],
                                              (other, "random", 100, 512))
                assert not backend.fits_batch([(fam, "hotcold", 100, 512)],
                                              (other, "hotcold", 100, 512))


def test_lane_shape_carries_policy():
    from repro.uvm.backends.pallas_backend import _lane_shape

    pages = np.arange(120) % 64
    for pol in ("lru", "random", "hotcold"):
        req = ReplayRequest(_mk_trace(pages), NoPrefetcher(),
                            UVMConfig(device_pages=32, eviction=pol))
        fam, shape_pol, t, sp = _lane_shape(req)
        assert (fam, shape_pol, t) == ("demand", pol, 120)


def test_pack_lanes_cobuckets_policies_not_families():
    """Interleaved cells of every eviction policy and equal shapes pack
    into one batch, one policy's lanes side by side; cells of two
    families never share a batch."""
    backend = PallasReplayBackend()
    pages = np.arange(200) % 64
    policies = ("lru", "random", "hotcold", "lru", "random", "hotcold")
    reqs = [ReplayRequest(_mk_trace(pages), NoPrefetcher(),
                          UVMConfig(device_pages=48, eviction=pol))
            for pol in policies]
    batch, = backend.pack_lanes(reqs)
    assert sorted(batch) == list(range(len(reqs)))
    in_order = [reqs[i].config.eviction for i in batch]
    assert in_order == sorted(in_order, reverse=True)
    # a second family: as many batches as families, each of one family
    reqs += [ReplayRequest(_mk_trace(pages), TreePrefetcher(),
                           UVMConfig(device_pages=48, eviction=pol))
             for pol in policies]
    batches = backend.pack_lanes(reqs)
    assert sorted(i for b in batches for i in b) == list(range(len(reqs)))
    assert len(batches) == 2
    for b in batches:
        assert len({lane_family(reqs[i].prefetcher) for i in b}) == 1
        assert {reqs[i].config.eviction for i in b} == set(policies)


def test_policy_lane_batches_match_numpy():
    """One replay() call covering every (family, policy) bucket under
    oversubscription equals independent NumPy replays."""
    perm = (np.arange(2 * 512) * 7) % (2 * 512)
    cases = [(pf, pol)
             for pf in ("none", "block", "tree", "learned", "oracle")
             for pol in ("random", "hotcold")]

    def build(pf, pol):
        tr = _mk_trace(np.concatenate([perm, perm + 1024]))
        config = UVMConfig(device_pages=600, mshr_entries=16, eviction=pol)
        return ReplayRequest(tr, golden_prefetcher(pf, tr, config), config)

    backend = get_backend("pallas")
    requests = [build(pf, pol) for pf, pol in cases]
    assert all(backend.can_replay(r) for r in requests)
    got = backend.replay(requests)
    want = [dispatch(build(pf, pol), "numpy") for pf, pol in cases]
    for (pf, pol), g, w in zip(cases, got, want):
        assert g.backend == "pallas" and g.eviction == pol
        assert w.pages_evicted > 0, "vacuous: no eviction churn"
        _assert_equivalent(g, w, context=f"{pf}/{pol}")


def test_lane_family_buckets():
    assert lane_family(NoPrefetcher()) == "demand"
    assert lane_family(BlockPrefetcher()) == "demand"
    assert lane_family(TreePrefetcher()) == "tree"
    pages = np.arange(10, dtype=np.int64)
    assert lane_family(OraclePrefetcher(pages)) == "oracle/96"
    tr = _mk_trace(pages)
    assert lane_family(
        golden_prefetcher("learned", tr, UVMConfig())) == "learned"

    class Unknown(NoPrefetcher):
        pass

    assert lane_family(Unknown()) is None


def test_bucketing_reuses_kernel_shapes():
    assert _bucket(1, 64) == 64
    assert _bucket(64, 64) == 64
    assert _bucket(65, 64) == 128
    assert _bucket(3, 1) == 4
    assert _bucket(1, 1) == 1


def test_pack_lanes_respects_budgets():
    backend = PallasReplayBackend()
    reqs = [_req(np.arange(50)) for _ in range(MAX_LANES_PER_BATCH + 3)]
    batches = backend.pack_lanes(reqs)
    assert sum(len(b) for b in batches) == len(reqs)
    assert sorted(i for b in batches for i in b) == list(range(len(reqs)))
    assert all(len(b) <= MAX_LANES_PER_BATCH for b in batches)
    assert len(batches) == 2


def _mixed_family_requests():
    pages = np.arange(200) % 64
    tr = _mk_trace(pages)
    config = UVMConfig()
    reqs = []
    for name in ("none", "tree", "block", "learned", "oracle",
                 "tree", "none", "learned", "oracle", "block"):
        reqs.append(ReplayRequest(_mk_trace(pages),
                                  golden_prefetcher(name, tr, config),
                                  config))
    return reqs


def test_pack_lanes_never_cobuckets_families():
    """Interleaved cells of every prefetcher family pack into
    family-homogeneous batches covering every request exactly once."""
    backend = PallasReplayBackend()
    reqs = _mixed_family_requests()
    batches = backend.pack_lanes(reqs)
    assert sorted(i for b in batches for i in b) == list(range(len(reqs)))
    for b in batches:
        fams = {lane_family(reqs[i].prefetcher) for i in b}
        assert len(fams) == 1, f"mixed-family batch: {fams}"
    # 4 families -> exactly 4 batches (shapes are identical, so nothing
    # else may force a flush)
    assert len(batches) == 4


# ---------------------------------------------------------------------------
# multi-lane equivalence (deterministic)
# ---------------------------------------------------------------------------

def test_lane_batch_matches_numpy_mixed_cells():
    """One batch mixing ragged lengths, both demand-family prefetchers,
    an oversubscribed cell, and a tight-MSHR fault storm."""
    rng = np.random.default_rng(7)
    cases = [
        # cyclic sweep, on-demand
        (np.tile(np.arange(300), 3), NoPrefetcher, None, 64),
        # block prefetch over strided faults
        (np.arange(0, 2000, 7), BlockPrefetcher, None, 64),
        # oversubscribed: working set ~2x capacity, LRU churn
        (np.tile(np.arange(400), 4), NoPrefetcher, 180, 64),
        # oversubscribed + block batches
        (np.tile(np.arange(500), 2), BlockPrefetcher, 300, 64),
        # clustered fault storm under a tiny MSHR
        (rng.integers(0, 4000, size=700), NoPrefetcher, None, 4),
        # short ragged lane
        (np.array([5, 5, 5, 900, 5]), BlockPrefetcher, None, 64),
    ]
    requests = [_req(pages, pf=pf_cls(), cap=cap, mshr=mshr)
                for pages, pf_cls, cap, mshr in cases]
    backend = get_backend("pallas")
    assert all(backend.can_replay(r) for r in requests)
    got = backend.replay(requests)
    want = [dispatch(_req(pages, pf=pf_cls(), cap=cap, mshr=mshr), "numpy")
            for pages, pf_cls, cap, mshr in cases]
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.backend == "pallas"
        _assert_equivalent(g, w, context=f"lane {i}")


def test_all_family_lane_replay_matches_numpy():
    """Every prefetcher family through the lanes in one replay() call —
    tree escalation churn under oversubscription, learned decision
    streams, oracle lookahead windows — equals independent NumPy
    replays."""
    rng = np.random.default_rng(11)
    perm = (np.arange(3 * 512) * 7) % (3 * 512)
    cases = [
        ("tree", np.arange(0, 2000, 3), None, 64),
        ("tree", perm.repeat(2), 700, 16),      # escalate + evict churn
        ("learned", np.tile(np.arange(350), 3), None, 64),
        ("learned", np.tile(np.arange(400), 4), 180, 64),
        ("oracle", rng.integers(0, 3000, size=500), None, 64),
        ("oracle", np.tile(np.arange(400), 3), 220, 64),
        ("none", np.tile(np.arange(300), 2), None, 64),
        ("block", np.arange(0, 1500, 5), 200, 64),
    ]

    def build(name, pages):
        tr = _mk_trace(np.asarray(pages, dtype=np.int64))
        return tr, golden_prefetcher(name, tr, UVMConfig())

    backend = get_backend("pallas")
    requests = []
    for name, pages, cap, mshr in cases:
        tr, pf = build(name, pages)
        requests.append(ReplayRequest(
            tr, pf, UVMConfig(device_pages=cap, mshr_entries=mshr)))
    assert all(backend.can_replay(r) for r in requests)
    got = backend.replay(requests)
    want = []
    for name, pages, cap, mshr in cases:
        tr, pf = build(name, pages)
        want.append(dispatch(ReplayRequest(
            tr, pf, UVMConfig(device_pages=cap, mshr_entries=mshr)),
            "numpy"))
    for (name, _, cap, _), g, w in zip(cases, got, want):
        assert g.backend == "pallas"
        _assert_equivalent(g, w, context=f"{name} cap={cap}")


# ---------------------------------------------------------------------------
# lockstep: a lane batch equals each of its lanes replayed alone
# ---------------------------------------------------------------------------

#: the prefetcher a lockstep case runs for each lane family
LOCKSTEP_PREFETCHER = {"demand": "block", "tree": "tree",
                       "learned": "learned", "oracle": "oracle"}
MT_TEST_BOUNDARY = 2 * ROOT_PAGES


def _ragged_lanes():
    """Three lanes of unequal length (a 4-lane batch with one padding
    lane): two evict, at different steps, and one never does."""
    perm = (np.arange(320) * 7) % 320
    return [(np.tile(perm, 2), 140),
            (np.arange(0, 1500, 4) % 480, 90),
            (np.random.default_rng(3).integers(0, 400, size=230), None)]


def _mk_mt_trace(pages0, pages1):
    """Two page streams as one interleaved two-tenant trace, tenant 1
    rebased above a root-aligned boundary (the interleaver's layout)."""
    pages0 = np.asarray(pages0, dtype=np.int64)
    pages1 = np.asarray(pages1, dtype=np.int64) + MT_TEST_BOUNDARY
    na, nb = len(pages0), len(pages1)
    keys = np.concatenate([np.arange(1, na + 1) * nb,
                           np.arange(1, nb + 1) * na])
    pages = np.concatenate([pages0, pages1])[np.argsort(keys,
                                                        kind="stable")]
    recs = make_records(len(pages))
    recs["page"] = pages
    return Trace("synth-mt", recs, {}, {}, len(pages) * 100,
                 meta={"mt": {"benches": ["A", "B"], "tenants": 2,
                              "boundary": MT_TEST_BOUNDARY}})


def _observed(st):
    """Everything a row reads from a lane's replay."""
    fields = {f: getattr(st, f) for f in INT_FIELDS + (
        "cycles", "pcie_bytes", "backend", "tenant_hits")}
    fields["step_clocks"] = (None if st.step_clocks is None
                             else st.step_clocks.tolist())
    return fields


def _lockstep_cases():
    cases = {}
    for family, pf_name in LOCKSTEP_PREFETCHER.items():
        for policy in ("lru", "random", "hotcold"):
            cases[f"{family}-{policy}-ragged"] = [
                (_mk_trace(pages), pf_name, cap, policy, None, None)
                for pages, cap in _ragged_lanes()]
    # equal lengths and no padding lane: what a sweep grid over one
    # trace packs
    sweep = np.tile((np.arange(400) * 3) % 400, 2)
    cases["demand-lru-equal"] = [
        (_mk_trace(sweep), pf_name, cap, "lru", None, None)
        for pf_name in ("none", "block") for cap in (150, 250)]
    rng = np.random.default_rng(5)
    a, b = rng.integers(0, 700, 300), np.tile(np.arange(350), 2)
    cases["tree-hotcold-mt"] = [
        (_mk_mt_trace(a, b), "tree", 240, "hotcold", None, (100, 100)),
        (_mk_mt_trace(b[:500], a), "tree", 180, "hotcold", None, None),
        (_mk_trace(a), "tree", 120, "hotcold", None, None)]
    cases["block-random-steps"] = [
        (_mk_trace(pages), "block", cap, "random", bounds, None)
        for (pages, cap), bounds in zip(_ragged_lanes(), (
            np.array([0, 40, 40, 300, 600]), None,
            np.arange(10, 231, 20)))]
    return cases


LOCKSTEP_CASES = _lockstep_cases()


@pytest.mark.parametrize("case", list(LOCKSTEP_CASES))
def test_lockstep_batch_equals_lanes_alone(case):
    """A lane batch replays every lane in lockstep: each of its rows is
    bit-equal to that lane replayed alone (a 1-lane batch), and agrees
    with the NumPy backend.  Every family under every policy, on lanes of
    unequal length with a padding lane, whose evictions fall at different
    steps; plus equal lengths, a multi-tenant and a step-clock batch."""
    lanes = LOCKSTEP_CASES[case]

    def build(i):
        trace, pf_name, cap, policy, bounds, quotas = lanes[i]
        config = UVMConfig(device_pages=cap, mshr_entries=16,
                           eviction=policy, tenant_pages=quotas)
        return ReplayRequest(trace, golden_prefetcher(pf_name, trace,
                                                      config), config,
                             step_bounds=bounds)

    backend = get_backend("pallas")
    requests = [build(i) for i in range(len(lanes))]
    assert all(backend.can_replay(r) for r in requests)
    assert len(backend.pack_lanes(requests)) == 1, "not one batch"
    together = backend.replay(requests)
    for i, got in enumerate(together):
        alone, = backend.replay([build(i)])
        assert _observed(got) == _observed(alone), f"{case} lane {i}"
        want = dispatch(build(i), "numpy")
        _assert_equivalent(got, want, context=f"{case} lane {i}")
        if want.tenant_hits is not None:
            assert got.tenant_hits == want.tenant_hits, f"{case} lane {i}"
        if want.step_clocks is not None:
            np.testing.assert_allclose(got.step_clocks, want.step_clocks,
                                       rtol=1e-6)
    evicted = [st.pages_evicted for st in together]
    assert len({e for e in evicted if e > 0}) >= 2 or case.endswith(
        "-equal"), f"vacuous: lanes evict alike {evicted}"


# ---------------------------------------------------------------------------
# mixed eviction policies: one batch, each lane under its own policy
# ---------------------------------------------------------------------------

#: the sets of eviction policies a mixed-policy batch runs
POLICY_SETS = (("lru", "random"), ("hotcold", "lru"), ("hotcold", "random"),
               ("hotcold", "lru", "random"))


def _mixed_policy_cases():
    """Ragged lanes that all evict, at different steps, taking a set's
    policies in turn; plus a multi-tenant and a step-clock batch."""
    lanes = [(pages, 120 if cap is None else cap)
             for pages, cap in _ragged_lanes()]
    cases = {}
    for pf_name in ("none", "block", "tree", "learned", "oracle"):
        for pols in POLICY_SETS:
            cases[f"{pf_name}-{'+'.join(pols)}"] = [
                (_mk_trace(pages), pf_name, cap, pols[i % len(pols)], None,
                 None) for i, (pages, cap) in enumerate(lanes)]
    rng = np.random.default_rng(5)
    a, b = rng.integers(0, 700, 300), np.tile(np.arange(350), 2)
    cases["tree-mt"] = [
        (_mk_mt_trace(a, b), "tree", 240, "random", None, (100, 100)),
        (_mk_mt_trace(b[:500], a), "tree", 180, "hotcold", None, None),
        (_mk_trace(a), "tree", 120, "lru", None, None)]
    cases["block-steps"] = [
        (_mk_trace(pages), "block", cap, pol, bounds, None)
        for (pages, cap), pol, bounds in zip(
            lanes, ("hotcold", "random", "lru"),
            (np.array([0, 40, 40, 300, 600]), None,
             np.arange(10, 231, 20)))]
    return cases


MIXED_POLICY_CASES = _mixed_policy_cases()


@pytest.mark.parametrize("case", list(MIXED_POLICY_CASES))
def test_mixed_policy_batch_equals_lanes_alone(case, monkeypatch):
    """Lanes of different eviction policies share one lockstep batch, and
    each of its rows is bit-equal to that lane replayed alone and agrees
    with the NumPy backend: each lane evicts the victims its own policy
    picks.  Only the mixed batch's program reads a per-lane policy
    column; a single-policy batch builds its program without one."""
    from repro.uvm.backends import pallas_backend

    lanes = MIXED_POLICY_CASES[case]
    built = []
    build_fn = pallas_backend._lane_replay_fn

    def spy(family, policies, *shape):
        fn = build_fn(family, policies, *shape)

        def call(*args):
            built.append((policies, args[-1].shape[1]))
            return fn(*args)
        return call

    monkeypatch.setattr(pallas_backend, "_lane_replay_fn", spy)

    def build(i, policy=None):
        trace, pf_name, cap, pol, bounds, quotas = lanes[i]
        config = UVMConfig(device_pages=cap, mshr_entries=16,
                           eviction=policy or pol, tenant_pages=quotas)
        return ReplayRequest(trace, golden_prefetcher(pf_name, trace,
                                                      config), config,
                             step_bounds=bounds)

    backend = get_backend("pallas")
    requests = [build(i) for i in range(len(lanes))]
    policies = tuple(sorted({r.config.eviction for r in requests}))
    assert len(policies) > 1
    assert all(backend.can_replay(r) for r in requests)
    assert len(backend.pack_lanes(requests)) == 1, "not one batch"
    together = backend.replay(requests)
    assert built == [(policies, _N_IPARAMS + 1)]
    for i, got in enumerate(together):
        alone, = backend.replay([build(i)])
        assert built[-1] == ((requests[i].config.eviction,), _N_IPARAMS)
        assert _observed(got) == _observed(alone), f"{case} lane {i}"
        want = dispatch(build(i), "numpy")
        _assert_equivalent(got, want, context=f"{case} lane {i}")
        if want.tenant_hits is not None:
            assert got.tenant_hits == want.tenant_hits, f"{case} lane {i}"
        if want.step_clocks is not None:
            np.testing.assert_allclose(got.step_clocks, want.step_clocks,
                                       rtol=1e-6)
    evicted = [st.pages_evicted for st in together]
    assert min(evicted) > 0, f"vacuous: a lane never evicts {evicted}"
    assert len(set(evicted)) >= 2, f"vacuous: lanes evict alike {evicted}"
    # the same lanes under one policy: no per-lane policy column
    backend.replay([build(i, policies[0]) for i in range(len(lanes))])
    assert built[-1] == ((policies[0],), _N_IPARAMS)


# ---------------------------------------------------------------------------
# property-based lane packing (skipped when hypothesis is absent)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st_

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - degraded environment
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    _cell = st_.tuples(
        st_.lists(st_.integers(0, 600), min_size=1, max_size=120),
        st_.sampled_from(["none", "block", "tree", "learned", "oracle"]),
        st_.sampled_from([None, 48, 200]),
        st_.sampled_from(["lru", "random", "hotcold"]),
    )

    @settings(max_examples=15, deadline=None)
    @given(st_.lists(_cell, min_size=1, max_size=5))
    def test_lane_batch_property(cells):
        """A lane-batched pallas replay of N random cells — every
        prefetcher family and eviction policy — equals N independent
        NumPy replays on every integer counter; ragged lengths and
        oversubscribed (cap=48/200) cells included.  Interleaved families
        exercise the family-homogeneous packing, and interleaved policies
        share batches."""
        def build(spec):
            pages, pf_name, cap, eviction = spec
            tr = _mk_trace(np.asarray(pages, dtype=np.int64))
            config = UVMConfig(device_pages=cap, mshr_entries=64,
                               eviction=eviction)
            return ReplayRequest(tr, golden_prefetcher(pf_name, tr, config),
                                 config)

        backend = get_backend("pallas")
        requests = [build(c) for c in cells]
        assert all(backend.can_replay(r) for r in requests)
        for b in backend.pack_lanes(requests):
            assert len({lane_family(requests[i].prefetcher)
                        for i in b}) == 1
        got = backend.replay(requests)
        want = [dispatch(build(c), "numpy") for c in cells]
        for i, (g, w) in enumerate(zip(got, want)):
            _assert_equivalent(g, w, context=f"lane {i}/{cells[i][1:]}")

# ---------------------------------------------------------------------------
# JAX's persistent compilation cache: placed from outside, else fixed
# ---------------------------------------------------------------------------

def test_compile_cache_location(tmp_path, monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and the code then sets no cache
    of its own; without it the cache is a fixed, git-ignored directory
    inside the checkout."""
    import os

    import jax

    from repro import compile_cache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "untouched")
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == "untouched"

        monkeypatch.delenv(compile_cache.ENV)
        path = compile_cache.enable()
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable() == path         # fixed, idempotent
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
