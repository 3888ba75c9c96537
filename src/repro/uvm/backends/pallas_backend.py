"""jax_pallas multi-lane replay backend: GPU-resident grid replay.

Packs many compatible sweep cells into ONE lane-batched ``pl.pallas_call``:
one lane per (trace, config) cell, traces padded to the longest lane, and
per-lane residency/arrival/LRU-stamp state held as device arrays.  The
lanes of a batch advance in lockstep, one trace position per loop step.

Lowering
--------
On every platform the kernel body is lowered by XLA, not by Mosaic:
``pallas_call(..., interpret=True)`` takes Pallas's discharge path,
which turns the refs into arrays.  The grid has one step, whose blocks
are the whole ``(n_lanes, ...)`` batch.  Inside it, one ``fori_loop``
over trace positions runs to the batch's longest lane, and each step
advances every lane by one access: the per-lane replay is
``jax.vmap``-ed over the lane axis, and the eviction loop runs until no
lane has a victim left.  A window of a lane's state (a basic block, a
root window) is sliced from the lane's own row of the batch, one dynamic
slice per lane: vmapped with per-lane starts, XLA:TPU would run it as a
loop over the lanes or as an element-wise scatter.  A lane past its last
access keeps its counters, clock and stall buffer (its page-sized state
is never read again), and a lane that has no victim left evicts nothing
while others do.  Each op of a step is bound by its fixed device
latency, not its bytes, so L lanes in lockstep cost far less than L
lanes one after another.  A 1-lane batch calls the per-lane replay
directly, without ``vmap``: its loop runs to its own length with
unbatched gathers and scatters and no per-lane selects, so a lane alone
pays nothing for lockstep.  On a TPU that is one compiled device program
per batch shape; on a CPU host it is the same program through XLA:CPU,
which is what the tests run under ``JAX_PLATFORMS=cpu``.  Mosaic refuses
this kernel as written (its float64 timing state; see ``README.md``),
and lowering it there waits on a 32-bit timing model.

Packable cells and lane families
--------------------------------
Every paper-facing prefetcher replays *fully in-kernel* — far-fault
service windows, PCIe queueing, batch-DMA prefetches, MSHR stalls, and
LRU eviction under oversubscription with in-flight-victim reinsertion —
so ``none``/``block``/``tree``/``learned``/``oracle`` cells are all
pallas-eligible.  Cells are bucketed into **lane families** and a batch
is always family-homogeneous (each family is a different kernel with
different per-lane state and inputs):

* ``demand`` — ``NoPrefetcher`` / ``BlockPrefetcher``: the faulting 64 KB
  basic-block window is one 16-page slice compare (no extra lane state).
* ``tree`` — ``TreePrefetcher``: dense per-level node-occupancy count
  arrays (``span >> (4+lv)`` int32 per level, lv = 0..5, mirroring the
  NumPy ``_TreeAdapter``) ride in the lane carry; a fault classifies the
  2 MB root window and walks the >50% escalation levels in-kernel,
  emitting extras in the exact legacy order (per level, ascending page)
  so LRU stamps — and therefore eviction order — stay bit-equal.
* ``learned`` — ``LearnedPrefetcher``: the precomputed ``predict_trace``
  array (content-addressed by ``repro.uvm.predcache``) is fed into the
  lane as a per-access prefetch-decision input stream (page indices
  relative to the lane span, ``-1`` = no prediction), and the serialized
  inference-server gate (``clock >= next_free``) is one float64 carry.
* ``oracle`` — ``OraclePrefetcher``: the first-touch page stream and the
  per-access stream position (a pure function of the access index) are
  precomputed host-side; each access scans a ``lookahead``-wide window of
  the stream for up to 16 non-resident pages, twice on faults (batch DMA
  then continuous), exactly like the legacy object.  Lanes with different
  ``lookahead`` are different families (the window width is a static
  kernel shape).

The **eviction policy** (``UVMConfig.eviction``, see
``repro.uvm.eviction``) is per-lane, like tenancy: one batch may mix
policies.  The batch's *set* of policies is static structure — it adds
the extra per-lane carries (``random`` insert-time priority draws,
``hotcold`` touch-frequency counts) and the victim keys the eviction
loop computes — and a mixed batch reads each lane's policy from its
parameter row and picks that lane's victim with its own key.  A batch of
one policy builds the single-policy program.  ``_lane_shape`` is
(family, policy, length, span): ``pack_lanes`` sorts by it, so a batch
holds as few policies as its lanes allow.

Stateful-prefetcher cells the backend still declines (oversized spans,
too-long traces, timeline recording) keep their exact NumPy adapters; the
scheduler in ``repro.uvm.sweep`` routes those cells to the ``numpy``
backend per cell, and the result rows record which backend actually ran.

Exactness
---------
Every float chain in the kernel replays the legacy loop's IEEE-754
operation order in float64 (the lane functions are traced under
``jax.enable_x64``), including a branch-free emulation of
CPython's float floor-division in the fault-service window computation
and the sequential ``t += page_tx`` arrival chain of non-batch (oracle
continuous) prefetches.  Integer counters are therefore exact and
cycles/pcie_bytes agree with the legacy engine to well inside the golden
1e-6 relative tolerance (bit-equal in practice);
``tests/test_uvm_golden.py`` pins this per golden cell for every family,
``tests/test_backends.py`` property-tests random lane batches against
independent NumPy replays, and ``tests/test_differential.py`` fuzzes all
registered backend pairs.

The per-lane state (arrival/stamp/pfu spans, tree counts) is carried
as ``(n_lanes, ...)`` arrays through the ``lax.fori_loop`` over trace
positions.  A Mosaic lowering would move the span state into scratch
refs; the lane packing, parameter blocks, and stats layout here are
already shaped for that.
"""
from __future__ import annotations

import functools
import types
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro import compile_cache, obs
from repro.traces.trace import BASIC_BLOCK_PAGES, ROOT_PAGES
from repro.uvm.eviction import (EVICTION_POLICIES, SCORE_MULT_1,
                                SCORE_MULT_2, SCORE_SEED_MULT,
                                resolve_tenancy)
from repro.uvm.prefetchers import (BlockPrefetcher, LearnedPrefetcher,
                                   NoPrefetcher, OraclePrefetcher,
                                   Prefetcher, TreePrefetcher)
from repro.uvm.replay_core import (ReplayBackend, ReplayRequest,
                                   cycles_per_access, dense_bounds,
                                   device_held_by_parent, require_device)
from repro.uvm.simulator import UVMStats, _tenant_accesses

#: lane-family kind per exact prefetcher type — the single source of
#: truth the scheduler derives its name-level family map from (oracle
#: lanes additionally carry their lookahead in the full family id)
FAMILY_BY_TYPE = {
    NoPrefetcher: "demand",
    BlockPrefetcher: "demand",
    TreePrefetcher: "tree",
    LearnedPrefetcher: "learned",
    OraclePrefetcher: "oracle",
}

#: prefetchers a pallas lane can replay entirely in-kernel
PACKABLE_PREFETCHERS = tuple(FAMILY_BY_TYPE)

#: hard per-lane page-span ceiling (beyond it the dense lane state would
#: dwarf the batch; such cells fall back to the NumPy path per cell)
MAX_LANE_SPAN_PAGES = 1 << 20

#: lane-batch shape budgets: lanes per kernel launch, total padded state
#: (lanes x span pages) and total padded trace positions (lanes x t_max)
MAX_LANES_PER_BATCH = 32
MAX_BATCH_STATE_PAGES = 1 << 23
MAX_BATCH_ACCESSES = 1 << 24

#: per-lane trace-length ceiling.  Must stay well below int32 range /
#: the max per-access touch-counter growth: the kernel's LRU stamps are
#: int32.  Demand/learned/oracle lanes grow the counter by at most
#: 1 + 16 + 16 = 33 per access (2^24 * 33 ~ 2^29, 4x headroom under
#: 2^31); a tree fault can stamp a whole 2 MB root window (1 + 511 per
#: access worst case), so tree lanes cap at 2^21 (2^21 * 512 = 2^30).
MAX_LANE_ACCESSES = MAX_BATCH_ACCESSES
MAX_TREE_LANE_ACCESSES = 1 << 21

#: oracle lookahead is a static kernel shape (the per-access window scan
#: width); absurd lookaheads fall back rather than bloat the kernel
MAX_ORACLE_LOOKAHEAD = 512

#: the legacy OraclePrefetcher emits at most 16 extras per callback
ORACLE_MAX_EXTRAS = 16

#: per-lane step-clock window ceiling (``ReplayRequest.step_bounds``):
#: the per-step segment-max carry is ``steps_len + 1`` float64 per lane,
#: so absurd window counts fall back to the NumPy path instead of
#: bloating the batch (serve traces are bounded well below this by
#: ``repro.offload.serve_trace.MAX_SERVE_STEPS``)
MAX_LANE_STEPS = 1 << 16

_N_FPARAMS = 8       # cpa, page_tx, far_fault, ptw, pcie_lat, pfo, extra, page_size
_N_IPARAMS = 9       # n_accesses, device_pages(-1=uncapped), mshr, has_block,
#                      n_ft, lane-lo mod 2^32 (random-policy priority draws),
#                      tenant boundary (dense; IMAX = single-tenant lane),
#                      q0, q1 (per-tenant quota pages; q0 = -1 = shared mode);
#                      a mixed-policy batch appends one more column: the
#                      lane's index into the batch's sorted policies
STAT_FIELDS = ("cycles", "hits", "late", "faults", "prefetch_issued",
               "prefetch_used", "pages_migrated", "pages_evicted",
               "pcie_bytes")
#: extra per-lane stat column of multi-tenant kernels (``mt=True``):
#: tenant-0 hits, appended after STAT_FIELDS (tenant-1 hits = hits - t0)
MT_STAT_FIELDS = ("hits_t0",)

#: lane-family max trace lengths (see MAX_LANE_ACCESSES note above)
_FAMILY_MAX_ACCESSES = {
    "demand": MAX_LANE_ACCESSES,
    "tree": MAX_TREE_LANE_ACCESSES,
    "learned": MAX_LANE_ACCESSES,
    "oracle": MAX_LANE_ACCESSES,
}


def lane_family(pf: Prefetcher) -> Optional[str]:
    """Lane-family bucket of a prefetcher, or None when unpackable.

    A lane batch is always family-homogeneous: each family is a distinct
    kernel with different per-lane state/inputs, so the scheduler and
    :meth:`PallasReplayBackend.fits_batch` must never co-bucket two
    families.  Oracle lanes carry their lookahead in the family id (the
    scan-window width is a static kernel shape).
    """
    family = FAMILY_BY_TYPE.get(type(pf))    # exact type: unknown
    if family == "oracle":                   # subclasses are unpackable
        return f"oracle/{int(pf.lookahead)}"
    return family


def _family_kind(family: str) -> str:
    """Kernel kind of a family id (strips the oracle lookahead suffix)."""
    return family.split("/")[0]


def policy_label(policies: Sequence[str]) -> str:
    """The eviction policies of a lane batch, sorted and joined by ``+``
    (``hotcold+random``): the ``policy`` attribute of its spans."""
    return "+".join(sorted(set(policies)))


def _bucket(n: int, floor: int) -> int:
    """Round up to the next power of two (>= floor) so repeated batches of
    similar shape reuse one compiled kernel."""
    b = max(floor, 1)
    while b < n:
        b <<= 1
    return b


@functools.lru_cache(maxsize=None)
def _lane_replay_fn(family: str, policies: Tuple[str, ...], n_lanes: int,
                    t_max: int, span: int, buf_len: int, ft_len: int,
                    lookahead: int, steps_len: int, mt: bool):
    """Build (and cache) the jitted multi-lane replay for one batch shape.

    ``family`` is the kernel kind (demand/tree/learned/oracle); ``ft_len``
    and ``lookahead`` are only meaningful for oracle lanes (0 otherwise).
    ``policies`` is the sorted tuple of the eviction policies the batch's
    lanes run under.  The set is static structure: it decides the extra
    per-lane carries (``random`` priority draws, ``hotcold`` frequency
    counts) and which victim keys ``ebody`` computes.  The policy of a
    lane is *per-lane dynamic*, like tenancy: a batch of several policies
    reads each lane's index into ``policies`` from the column
    ``_N_IPARAMS`` of its int32 parameter row, and picks each lane's
    victim with its own policy's key (a per-lane select, then one
    argmin).  A lane may update a carry its policy never reads; its
    victims, and so its stats, stay those of the lane alone.  A batch of
    one policy builds the single-policy program, with no such column.
    ``n_lanes == 1`` builds one lane's own loop over its accesses; a
    wider batch runs its lanes in lockstep (module docstring,
    "Lowering").

    ``mt`` enables multi-tenant lane support (``repro.traces.interleave``):
    per-lane tenancy parameters (dense region boundary + per-tenant
    quotas), a tenant-0 residency carry, per-tenant quota eviction with
    tenant-masked victim selection, and a tenant-0 hit-count carry drained
    into one extra stat column (:data:`MT_STAT_FIELDS`).  Tenancy is
    *per-lane dynamic*: a single-tenant lane of an mt batch rides with
    boundary = IMAX and ``q0 = -1``, which makes every tenant branch a
    no-op — its stats stay bit-identical to the ``mt=False`` kernel, so
    mixed batches need no extra homogeneity rule.  ``mt=False`` builds
    the exact pre-tenancy kernel.

    ``steps_len > 0`` enables in-kernel step-clock capture
    (``ReplayRequest.step_bounds``): each access carries its window id in
    an extra int32 input stream, and a ``steps_len + 1`` float64 carry
    records the post-access clock per window (the last write of a window
    is the clock after its last access — exactly the legacy recording
    point).  Slot ``steps_len`` is a trash slot for accesses past the
    last bound, for no-bounds lanes of a mixed batch, and for a lane
    past its last access while longer lanes run on.  The clock
    chain itself is untouched, so stats stay bit-identical with capture
    on; ``steps_len == 0`` builds the exact pre-capture kernel (no extra
    input, single output).
    """
    import jax
    import jax.numpy as jnp
    from jax import custom_batching
    from jax.experimental import pallas as pl

    obs.count("lane.program_builds")       # runs once per cached shape

    blk_pages = BASIC_BLOCK_PAGES
    blk_shift = blk_pages.bit_length() - 1
    levels = TreePrefetcher.LEVELS
    i32 = jnp.int32
    u32 = jnp.uint32
    IMAX_NP = np.iinfo(np.int32).max
    IMAX64_NP = np.iinfo(np.int64).max
    hotcold = "hotcold" in policies
    randomp = "random" in policies
    mixed = len(policies) > 1
    # the random victim key is (prio << 21) | slot: every state slot
    # (span + oracle trash) must fit the low 21 bits or slot indices
    # would bleed into the priority bits and silently reorder victims —
    # raising MAX_LANE_SPAN_PAGES past 2^21 - 1 must fail loudly here
    assert span + 1 <= 1 << 21, (
        f"lane span {span} overflows the random-policy victim key; "
        "widen the slot field before raising MAX_LANE_SPAN_PAGES")

    def _rand_score(pages_u32, draw_i32):
        """jnp port of ``repro.uvm.eviction.eviction_scores`` — the exact
        same uint32 wraparound chain, pinned equal by the golden and
        differential suites."""
        x = pages_u32 ^ (draw_i32.astype(u32) * u32(SCORE_SEED_MULT))
        x = x ^ (x >> u32(16))
        x = x * u32(SCORE_MULT_1)
        x = x ^ (x >> u32(15))
        x = x * u32(SCORE_MULT_2)
        x = x ^ (x >> u32(15))
        return x
    # oracle lanes get one extra "trash" slot at index ``span``: window
    # scatters direct every masked-off write there, so duplicate scatter
    # indices never land on a real page.  The slot reads as resident
    # (arrival 0.0) and is never the LRU victim (stamp pinned at IMAX).
    state_len = span + 1 if family == "oracle" else span
    # the batch's input blocks, one row per lane, in the order
    # ``_replay_batch`` passes them
    in_names = (["pages"]
                + {"learned": ["preds"], "oracle": ["ft", "pos"]}.get(
                    family, [])
                + (["sids"] if steps_len else []) + ["fp", "ip"])
    n_inputs = len(in_names)
    # the carry entries the eviction loop reads and writes
    ev_keys = ["arrival", "stamp", "pfu", "counter", "resident", "evicted",
               "wbacks", "pcie_free"]
    if mt:
        ev_keys.append("rc0")
    if hotcold:
        ev_keys.append("freq")
    if family == "tree":
        ev_keys.append("counts")
    # page-sized state a lane past its last access may let go stale:
    # nothing reads it again, and its step-clock writes go to the trash
    # slot.  Every other carry entry of such a lane is held as it was.
    page_state = ("arrival", "stamp", "pfu", "freq", "prio", "counts",
                  "steps")

    # Windows of a lane's state: a dynamic slice or update.  Under vmap,
    # JAX makes one with a per-lane start a gather or scatter, which
    # XLA:TPU runs as a loop over the lanes or, element by element, as a
    # serial scatter; so a batch slices or updates each lane's row of the
    # (n_lanes, pages) array on its own, one dynamic slice per lane.
    def _per_row(axis_size, in_batched, *args):
        return [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]

    def window(x, start, size):
        @custom_batching.custom_vmap
        def read(x, start):
            return jax.lax.dynamic_slice(x, (start,), (size,))

        @read.def_vmap
        def _(axis_size, in_batched, x, start):
            x, start = _per_row(axis_size, in_batched, x, start)
            # rows go into a fresh block one by one: concatenated, they
            # run slower on XLA:TPU and crash XLA:CPU's fusion emitters
            out = jnp.zeros((axis_size, size), x.dtype)
            for l in range(axis_size):
                out = jax.lax.dynamic_update_slice(
                    out, jax.lax.dynamic_slice(x, (i32(l), start[l]),
                                               (1, size)), (i32(l), i32(0)))
            return out, True

        return read(x, start)

    def put_window(x, vals, start):
        @custom_batching.custom_vmap
        def write(x, vals, start):
            return jax.lax.dynamic_update_slice(x, vals, (start,))

        @write.def_vmap
        def _(axis_size, in_batched, x, vals, start):
            x, vals, start = _per_row(axis_size, in_batched, x, vals, start)
            for l in range(axis_size):
                x = jax.lax.dynamic_update_slice(x, vals[l][None],
                                                 (i32(l), start[l]))
            return x, True

        return write(x, vals, start)

    def lane(L):
        """One lane's replay, as functions of its input rows ``L``
        (``in_names`` -> 1-D arrays): ``advance`` replays access ``t`` up
        to the eviction loop, whose ``econd``/``ebody`` follow, and
        ``finish`` drains the stall buffer into the stats row.  A 1-lane
        batch calls them directly; a wider batch calls them under
        ``jax.vmap``."""
        INF = jnp.float64(jnp.inf)
        IMAX = jnp.int32(IMAX_NP)
        IMAX64 = jnp.int64(IMAX64_NP)
        zero = jnp.int32(0)
        pages, fp, ip = L["pages"], L["fp"], L["ip"]
        cpa, page_tx, ff, ptw, pcie_lat = fp[0], fp[1], fp[2], fp[3], fp[4]
        pfo, extra_lat, page_size = fp[5], fp[6], fp[7]
        n = ip[0]
        cap = ip[1]
        mshr = ip[2]
        has_block = ip[3] > 0
        track_lru = cap >= 0
        if mt:
            # per-lane tenancy: dense boundary page (IMAX = single-tenant
            # lane: every page compares tenant 0 and the branches no-op),
            # per-tenant quotas (q0 < 0 = shared capacity)
            bnd = ip[6]
            q0 = ip[7]
            q1 = ip[8]
            tsplit = q0 >= 0
            slot_iota = jnp.arange(state_len, dtype=i32)
        if randomp:
            # absolute page ids mod 2^32: the random policy's priority
            # draws hash the absolute page, so all backends agree
            # whatever the lane's dense-span offset is
            lane_lo = ip[5].astype(u32)
            iota64 = jnp.arange(state_len, dtype=jnp.int64)

            def abs_page(slots):
                return lane_lo + slots.astype(u32)
        if steps_len:
            sids = L["sids"]
        if mixed:
            # the lane's own eviction policy, an index into ``policies``
            lane_pol = ip[_N_IPARAMS]

        # The legacy loop rounds every multiply before the dependent add,
        # but LLVM contracts ``a + b * c`` into a fused multiply-add
        # (single rounding, 1-ULP drift vs CPython) and neither
        # optimization_barrier nor a bitcast round-trip survives to
        # codegen.  ``abs`` does: it is an identity on these provably
        # non-negative products and fabs() breaks the fmul->fadd
        # contraction pattern, pinning the separately-rounded product.
        def _nofma(x):
            return jnp.abs(x)

        if family == "learned":
            preds = L["preds"]
        if family == "oracle":
            ft = L["ft"]
            posarr = L["pos"]
            n_ft = ip[4]
            look_iota = jnp.arange(lookahead, dtype=i32)

        def advance(t, s, active):
            arrival, stamp, pfu = s["arrival"], s["stamp"], s["pfu"]
            buf = s["buf"]
            counter = s["counter"]
            pcie_free = s["pcie_free"]
            if family == "tree":
                counts = list(s["counts"])
            if hotcold:
                freq = s["freq"]
            if randomp:
                prio = s["prio"]

            p = pages[t]
            clock = s["clock"] + cpa
            a = arrival[p]
            is_res = a < INF
            is_hit = is_res & (a <= clock)
            is_late = is_res & ~is_hit
            is_fault = ~is_res
            hits = s["hits"] + is_hit.astype(i32)
            late = s["late"] + is_late.astype(i32)
            faults = s["faults"] + is_fault.astype(i32)
            if mt:
                th0 = s["th0"] + (is_hit & (p < bnd)).astype(i32)
                rc0 = s["rc0"]

            # prefetched-but-unused consumption (False on faults by
            # construction: eviction clears the flag with the residency)
            used = s["used"] + pfu[p].astype(i32)
            pfu = pfu.at[p].set(False)

            # far-fault service window.  ``(clock // ff)`` in the legacy
            # loop is CPython float floor-division: fmod-based, so the
            # quotient is exact even when clock/ff rounds across an
            # integer — replay that algorithm branch-free (args positive).
            mod = jax.lax.rem(clock, ff)
            div = (clock - mod) / ff
            fd = jnp.floor(div)
            fd = jnp.where(div - fd > 0.5, fd + 1.0, fd)
            ready = _nofma((fd + 2.0) * ff) + ptw
            start = jnp.maximum(ready, pcie_free)
            arr_v = start + pcie_lat + page_tx

            # demand insert (fault) / LRU retouch (hit, late): both stamp
            # the page at the current touch counter
            arrival = arrival.at[p].set(jnp.where(is_fault, arr_v, a))
            stamp = stamp.at[p].set(counter)
            if hotcold:
                # touches since migration: reset at insert, +1 per touch
                freq = freq.at[p].set(jnp.where(is_fault, 0, freq[p] + 1))
            if randomp:
                # insert-time priority draw, seeded by the touch counter
                prio = prio.at[p].set(jnp.where(
                    is_fault, _rand_score(abs_page(p), counter), prio[p]))
            counter = counter + 1
            resident = s["resident"] + is_fault.astype(i32)
            if mt:
                rc0 = rc0 + (is_fault & (p < bnd)).astype(i32)
            migrated = s["migrated"] + is_fault.astype(i32)
            pcie_free = jnp.where(is_fault, start + page_tx, pcie_free)

            # outstanding-stall push: a fault waits on its own migration,
            # a late access on the in-flight page's arrival (<=1 per step,
            # so the buffer never overflows mshr+1 before the trim below)
            push = is_fault | is_late
            push_val = jnp.where(is_fault, arr_v, a)
            slot = jnp.argmax(buf)               # some empty (+inf) slot
            buf = buf.at[slot].set(jnp.where(push, push_val, buf[slot]))
            nbuf = s["nbuf"] + push.astype(i32)

            issued = s["issued"]

            if family == "tree":
                # the engine raises on_migrate([demand]) BEFORE on_fault,
                # so node occupancy includes the demand page when the
                # escalation walk below reads it (legacy double-counts it
                # again through ``pending`` — replayed exactly)
                for lv in range(levels + 1):
                    counts[lv] = counts[lv].at[p >> (blk_shift + lv)].add(
                        is_fault.astype(i32))

            if family in ("demand", "learned"):
                # block prefetcher on_fault: batch-DMA the faulting 64 KB
                # basic block's non-resident pages (the demand page is
                # already in flight, so the window compare excludes it)
                blk = (p // blk_pages) * blk_pages
                win = window(arrival, blk, blk_pages)
                mask = (win == INF) & is_fault & has_block
                k = jnp.sum(mask, dtype=i32)
                kf = k.astype(jnp.float64)
                ex_ready = clock + pfo + extra_lat
                ex_start = jnp.maximum(pcie_free, ex_ready)
                end = ex_start + _nofma(kf * page_tx)
                ex_arr = end + pcie_lat          # batch completes as one DMA
                arrival = put_window(arrival, jnp.where(mask, ex_arr, win),
                                     blk)
                pwin = window(pfu, blk, blk_pages)
                pfu = put_window(pfu, pwin | mask, blk)
                swin = window(stamp, blk, blk_pages)
                rank = counter + jnp.cumsum(mask, dtype=i32) - 1
                stamp = put_window(stamp, jnp.where(mask, rank, swin), blk)
                if hotcold:
                    fwin = window(freq, blk, blk_pages)
                    freq = put_window(freq, jnp.where(mask, 0, fwin), blk)
                if randomp:
                    uwin = abs_page(blk + jnp.arange(blk_pages, dtype=i32))
                    prwin = window(prio, blk, blk_pages)
                    prio = put_window(
                        prio, jnp.where(mask, _rand_score(uwin, rank), prwin),
                        blk)
                counter = counter + k
                resident = resident + k
                if mt:
                    # the 64 KB block never straddles the (root-aligned)
                    # tenant boundary: the whole batch is p's tenant
                    rc0 = rc0 + jnp.where(p < bnd, k, 0)
                migrated = migrated + k
                issued = issued + k
                pcie_free = jnp.where(k > 0, end, pcie_free)

            if family == "tree":
                # tree on_fault: classify the 2 MB root window, then the
                # >50% escalation walk.  Extras are emitted per level in
                # ascending page order (the legacy list order), which the
                # per-level cumsum ranks reproduce so LRU stamps match.
                root = (p // ROOT_PAGES) * ROOT_PAGES
                rwin = window(arrival, root, ROOT_PAGES)
                nonres = rwin == INF
                offs = jnp.arange(ROOT_PAGES, dtype=i32)
                rel = p - root
                in_blk = (offs >> blk_shift) == (rel >> blk_shift)
                m0 = in_blk & nonres & is_fault
                out_mask = m0
                pend = m0 | (offs == rel)        # about-to-arrive + demand
                rank = jnp.where(m0, jnp.cumsum(m0.astype(i32)) - 1, 0)
                k = jnp.sum(m0, dtype=i32)
                go = is_fault
                for lv in range(1, levels + 1):
                    span_lv = blk_pages << lv
                    in_node = (offs // span_lv) == (rel // span_lv)
                    node_abs = ((root + (rel // span_lv) * span_lv)
                                >> (blk_shift + lv))
                    cnt = (counts[lv][node_abs]
                           + jnp.sum(in_node & pend, dtype=i32))
                    fire = go & (cnt * 2 > span_lv)
                    ex = in_node & nonres & ~pend & fire
                    rank = jnp.where(
                        ex, k + jnp.cumsum(ex.astype(i32)) - 1, rank)
                    k = k + jnp.sum(ex, dtype=i32)
                    pend = pend | ex
                    out_mask = out_mask | ex
                    go = fire
                kf = k.astype(jnp.float64)
                ex_ready = clock + pfo + extra_lat
                ex_start = jnp.maximum(pcie_free, ex_ready)
                end = ex_start + _nofma(kf * page_tx)
                ex_arr = end + pcie_lat
                arrival = put_window(
                    arrival, jnp.where(out_mask, ex_arr, rwin), root)
                pwin = window(pfu, root, ROOT_PAGES)
                pfu = put_window(pfu, pwin | out_mask, root)
                swin = window(stamp, root, ROOT_PAGES)
                stamp = put_window(
                    stamp, jnp.where(out_mask, counter + rank, swin), root)
                if hotcold:
                    fwin = window(freq, root, ROOT_PAGES)
                    freq = put_window(freq, jnp.where(out_mask, 0, fwin), root)
                if randomp:
                    uwin = abs_page(root + offs)
                    prwin = window(prio, root, ROOT_PAGES)
                    prio = put_window(
                        prio,
                        jnp.where(out_mask,
                                  _rand_score(uwin, counter + rank), prwin),
                        root)
                counter = counter + k
                resident = resident + k
                if mt:
                    # the 2 MB root window is entirely on p's side of the
                    # root-aligned tenant boundary
                    rc0 = rc0 + jnp.where(p < bnd, k, 0)
                migrated = migrated + k
                issued = issued + k
                pcie_free = jnp.where(k > 0, end, pcie_free)
                # on_migrate of the batch: per-level node occupancy grows
                # by the per-node page counts of the scheduled window
                for lv in range(levels + 1):
                    node_span = blk_pages << lv
                    n_nodes = ROOT_PAGES // node_span
                    inc = jnp.sum(
                        out_mask.reshape(n_nodes, node_span).astype(i32),
                        axis=1, dtype=i32)
                    node0 = root >> (blk_shift + lv)
                    cwin = window(counts[lv], node0, n_nodes)
                    counts[lv] = put_window(counts[lv], cwin + inc, node0)

            if family == "learned":
                # LearnedPrefetcher.on_access: serialized inference server
                # — an access consumes the gate iff clock >= next_free
                # (whether or not a prefetch results), and only a valid,
                # non-demand, non-resident top-1 prediction migrates.
                # Runs after the fault path, so the prediction's residency
                # check sees the block batch, exactly like the legacy
                # callback order.
                next_free = s["next_free"]
                fire = clock >= next_free
                next_free = jnp.where(fire, clock + extra_lat, next_free)
                pred = preds[t]
                safe = jnp.maximum(pred, 0)
                do_pf = (fire & (pred >= 0) & (pred != p)
                         & (arrival[safe] == INF))
                ex_ready2 = clock + pfo + extra_lat
                ex_start2 = jnp.maximum(pcie_free, ex_ready2)
                end2 = ex_start2 + page_tx       # single-page transfer
                ex_arr2 = end2 + pcie_lat
                arrival = arrival.at[safe].set(
                    jnp.where(do_pf, ex_arr2, arrival[safe]))
                stamp = stamp.at[safe].set(
                    jnp.where(do_pf, counter, stamp[safe]))
                if hotcold:
                    freq = freq.at[safe].set(
                        jnp.where(do_pf, 0, freq[safe]))
                if randomp:
                    prio = prio.at[safe].set(jnp.where(
                        do_pf, _rand_score(abs_page(safe), counter),
                        prio[safe]))
                pfu = pfu.at[safe].set(do_pf | pfu[safe])
                counter = counter + do_pf.astype(i32)
                resident = resident + do_pf.astype(i32)
                if mt:
                    rc0 = rc0 + (do_pf & (safe < bnd)).astype(i32)
                migrated = migrated + do_pf.astype(i32)
                issued = issued + do_pf.astype(i32)
                pcie_free = jnp.where(do_pf, end2, pcie_free)

            if family == "oracle":
                # OraclePrefetcher: scan a lookahead window of the
                # first-touch stream (position precomputed per access) for
                # up to 16 non-resident pages, in stream order.  A fault
                # scans twice — on_fault (batch DMA) then on_access
                # (continuous, sequential per-page arrivals) — with the
                # second scan seeing the first's insertions.
                pos_t = posarr[t]
                base_valid = (pos_t + look_iota) < n_ft
                win_idx = window(ft, pos_t, lookahead)

                def scan(arrival, stamp, pfu, counter, resident, migrated,
                         issued, pcie_free, pol, rc0, active, batch):
                    got = arrival[win_idx]
                    nonres = base_valid & (got == INF) & active
                    csum = jnp.cumsum(nonres.astype(i32))
                    take = nonres & (csum <= ORACLE_MAX_EXTRAS)
                    k = jnp.sum(take, dtype=i32)
                    if mt:
                        # oracle lookahead windows can span both tenant
                        # regions: count the tenant-0 insertions directly
                        rc0 = rc0 + jnp.sum(take & (win_idx < bnd),
                                            dtype=i32)
                    rank = csum - 1              # emission order rank
                    kf = k.astype(jnp.float64)
                    ex_ready = clock + pfo + extra_lat
                    ex_start = jnp.maximum(pcie_free, ex_ready)
                    end = ex_start + _nofma(kf * page_tx)
                    if batch:
                        arr_vals = jnp.broadcast_to(end + pcie_lat,
                                                    (lookahead,))
                    else:
                        # legacy non-batch arrivals are the sequential
                        # ``t += page_tx`` chain — replay the exact fp
                        # additions, not ex_start + j * page_tx
                        chain = []
                        tv = ex_start
                        for _ in range(ORACLE_MAX_EXTRAS):
                            tv = tv + page_tx
                            chain.append(tv)
                        chain = jnp.stack(chain)
                        arr_vals = chain[jnp.clip(
                            rank, 0, ORACLE_MAX_EXTRAS - 1)] + pcie_lat
                    tgt = jnp.where(take, win_idx, span)   # span = trash
                    arrival = arrival.at[tgt].set(
                        jnp.where(take, arr_vals, 0.0))
                    stamp = stamp.at[tgt].set(
                        jnp.where(take, counter + rank, IMAX))
                    pfu = pfu.at[tgt].set(take)
                    pol = dict(pol)
                    if hotcold:
                        freq = pol["freq"]
                        pol["freq"] = freq.at[tgt].set(
                            jnp.where(take, 0, freq[tgt]))
                    if randomp:
                        prio = pol["prio"]
                        prw = _rand_score(abs_page(win_idx), counter + rank)
                        pol["prio"] = prio.at[tgt].set(
                            jnp.where(take, prw, prio[tgt]))
                    counter = counter + k
                    resident = resident + k
                    migrated = migrated + k
                    issued = issued + k
                    pcie_free = jnp.where(k > 0, end, pcie_free)
                    return (arrival, stamp, pfu, counter, resident,
                            migrated, issued, pcie_free, pol, rc0)

                # the policy carries the scans update
                pol = {}
                if hotcold:
                    pol["freq"] = freq
                if randomp:
                    pol["prio"] = prio
                rc0_c = rc0 if mt else zero
                (arrival, stamp, pfu, counter, resident, migrated, issued,
                 pcie_free, pol, rc0_c) = scan(arrival, stamp, pfu, counter,
                                               resident, migrated, issued,
                                               pcie_free, pol, rc0_c,
                                               is_fault, True)
                (arrival, stamp, pfu, counter, resident, migrated, issued,
                 pcie_free, pol, rc0_c) = scan(arrival, stamp, pfu, counter,
                                               resident, migrated, issued,
                                               pcie_free, pol, rc0_c,
                                               jnp.bool_(True), False)
                if mt:
                    rc0 = rc0_c
                if hotcold:
                    freq = pol["freq"]
                if randomp:
                    prio = pol["prio"]

            # MSHR pressure: beyond ``mshr`` outstanding stalls the clock
            # jumps to the oldest completion (single pop suffices: pushes
            # are <=1 per access and the buffer is trimmed every access)
            pop = nbuf > mshr
            mi = jnp.argmin(buf)
            clock = jnp.where(pop, jnp.maximum(clock, buf[mi]), clock)
            buf = buf.at[mi].set(jnp.where(pop, INF, buf[mi]))
            nbuf = nbuf - pop.astype(i32)

            if steps_len:
                # the clock is final for this access here (eviction never
                # moves it), so the window slot ends up holding the clock
                # after its last access — the legacy recording point.  A
                # lane past its last access writes the trash slot.
                sid = sids[t]
                if active is not None:
                    sid = jnp.where(active, sid, steps_len)
                steps = s["steps"].at[sid].set(clock)

            out = {
                "arrival": arrival, "stamp": stamp, "pfu": pfu, "buf": buf,
                "clock": clock, "pcie_free": pcie_free, "counter": counter,
                "resident": resident, "nbuf": nbuf,
                "hits": hits, "late": late, "faults": faults,
                "issued": issued, "used": used, "migrated": migrated,
                "evicted": s["evicted"], "wbacks": s["wbacks"],
            }
            if mt:
                out["rc0"] = rc0
                out["th0"] = th0
            if family == "learned":
                out["next_free"] = next_free
            if family == "tree":
                out["counts"] = tuple(counts)
            if hotcold:
                out["freq"] = freq
            if randomp:
                out["prio"] = prio
            if steps_len:
                out["steps"] = steps
            # the eviction loop runs while ``cont`` holds and the lane is
            # over its capacity; a lane past its last access takes no part
            cont = track_lru if active is None else track_lru & active
            return out, cont

        # eviction under oversubscription: the lane's policy picks the
        # victim (lru = min touch stamp, exact OrderedDict order; random =
        # min insert-time priority draw; hotcold = min (freq, stamp));
        # an in-flight victim is retouched at MRU and stops the loop
        def _allowed(c):
            """Per-tenant residency ceilings (Tenancy.allowed in
            int32) + the over-allowance flags of a quota-split lane."""
            rc0c = c["rc0"]
            rc1c = c["resident"] - rc0c
            spill = cap - q0 - q1
            a0 = q0 + jnp.maximum(0, spill - jnp.maximum(0, rc1c - q1))
            a1 = q1 + jnp.maximum(0, spill - jnp.maximum(0, rc0c - q0))
            return rc0c > a0, rc1c > a1

        def econd(c):
            if mt:
                over0, over1 = _allowed(c)
                return c["cont"] & jnp.where(
                    tsplit, over0 | over1, c["resident"] > cap)
            return c["cont"] & (c["resident"] > cap)

        def ebody(c, s, go):
            """One eviction of the access that left ``s``.  ``go`` (a
            lockstep batch only) is this lane's ``econd``: where it is
            false every write is a no-op, so the lane's state stays as it
            is while the batch's other lanes evict."""
            clock = s["clock"]
            arrival, stamp, pfu = c["arrival"], c["stamp"], c["pfu"]
            counter = c["counter"]
            res_mask = arrival < INF
            if mt:
                # quota split: trim whichever tenant is over its
                # allowance (tenant 0 first, like the legacy loop),
                # victim masked to that tenant's state slots; shared
                # mode keeps the unmasked single-tenant selection
                over0, _ = _allowed(c)
                u = jnp.where(over0, 0, 1)
                res_mask = res_mask & (
                    ~tsplit | ((slot_iota >= bnd).astype(i32) == u))
            if hotcold:
                fq = c["freq"]

            def victim_key(policy):
                if policy == "hotcold":
                    return jnp.where(
                        res_mask & (stamp < IMAX),
                        (fq.astype(jnp.int64) << 32)
                        | stamp.astype(jnp.int64), IMAX64)
                if policy == "random":
                    # prio is static while resident: read it from ``s``
                    return jnp.where(
                        res_mask & (stamp < IMAX),
                        (s["prio"].astype(jnp.int64) << 21) | iota64,
                        IMAX64)
                return jnp.where(res_mask, stamp, IMAX)

            if mixed:
                # each lane's own policy key, widened to int64 (an order-
                # and tie-preserving cast of the int32 lru key), then one
                # argmin: the victim, ties included, of the lane alone
                key = victim_key(policies[0]).astype(jnp.int64)
                for j, policy in enumerate(policies[1:], 1):
                    key = jnp.where(lane_pol == j,
                                    victim_key(policy).astype(jnp.int64), key)
                vi = jnp.argmin(key)
            else:
                vi = jnp.argmin(victim_key(policies[0]))
            v_arr = arrival[vi]
            in_flight = v_arr > clock
            # retouched (in flight) or evicted, on a lane that evicts
            retouch = in_flight if go is None else go & in_flight
            evict = ~in_flight if go is None else go & ~in_flight
            stamp = stamp.at[vi].set(
                jnp.where(retouch, counter, stamp[vi]))
            if hotcold:
                fq = fq.at[vi].add(retouch.astype(i32))
            counter = counter + retouch.astype(i32)
            arrival = arrival.at[vi].set(jnp.where(evict, INF, v_arr))
            pfu = pfu.at[vi].set(jnp.where(evict, False, pfu[vi]))
            ev = evict.astype(i32)
            resident = c["resident"] - ev
            evicted = c["evicted"] + ev
            # writeback traffic (half the evictions dirty)
            wb = evict & (evicted % 2 == 0)
            wbacks = c["wbacks"] + wb.astype(i32)
            pcie_free = c["pcie_free"] + jnp.where(wb, page_tx, 0.0)
            cont = ~in_flight if go is None else c["cont"] & ~retouch
            out = dict(c, cont=cont, arrival=arrival, stamp=stamp, pfu=pfu,
                       counter=counter, resident=resident, evicted=evicted,
                       wbacks=wbacks, pcie_free=pcie_free)
            if mt:
                out["rc0"] = c["rc0"] - (evict & (vi < bnd)).astype(i32)
            if hotcold:
                out["freq"] = fq
            if family == "tree":
                cts = list(c["counts"])
                for lv in range(levels + 1):
                    cts[lv] = cts[lv].at[vi >> (blk_shift + lv)].add(-ev)
                out["counts"] = tuple(cts)
            return out

        def finish(f):
            """Stats row (and step clocks) of the final state: every
            outstanding stall resolves (max over the buffer is the max
            over any heap-pop order)."""
            buf = f["buf"]
            tail = jnp.max(jnp.where(buf < jnp.inf, buf, -jnp.inf))
            clock = jnp.where(f["nbuf"] > 0,
                              jnp.maximum(f["clock"], tail), f["clock"])
            cols = [clock] + [f[k].astype(jnp.float64) for k in (
                "hits", "late", "faults", "issued", "used", "migrated",
                "evicted")]
            cols.append((f["migrated"] + f["wbacks"]).astype(jnp.float64)
                        * page_size)
            if mt:
                cols.append(f["th0"].astype(jnp.float64))
            row = jnp.stack(cols)
            if steps_len:
                return row, f["steps"][:steps_len]
            return row

        return types.SimpleNamespace(n=n, advance=advance, econd=econd,
                                     ebody=ebody, finish=finish)

    def init_state():
        zero = jnp.int32(0)
        init = {
            "arrival": jnp.full((state_len,), jnp.inf, dtype=jnp.float64),
            "stamp": jnp.zeros((state_len,), dtype=i32),
            "pfu": jnp.zeros((state_len,), dtype=jnp.bool_),
            "buf": jnp.full((buf_len,), jnp.inf, dtype=jnp.float64),
            "clock": jnp.float64(0.0), "pcie_free": jnp.float64(0.0),
            "counter": zero, "resident": zero, "nbuf": zero,
            "hits": zero, "late": zero, "faults": zero,
            "issued": zero, "used": zero, "migrated": zero,
            "evicted": zero, "wbacks": zero,
        }
        if mt:
            init["rc0"] = zero
            init["th0"] = zero
        if family == "oracle":
            # trash slot: reads resident, never the LRU victim
            init["arrival"] = init["arrival"].at[span].set(0.0)
            init["stamp"] = init["stamp"].at[span].set(IMAX_NP)
        if family == "learned":
            init["next_free"] = jnp.float64(0.0)
        if family == "tree":
            init["counts"] = tuple(
                jnp.zeros((span >> (blk_shift + lv),), dtype=i32)
                for lv in range(levels + 1))
        if hotcold:
            init["freq"] = jnp.zeros((state_len,), dtype=i32)
        if randomp:
            init["prio"] = jnp.zeros((state_len,), dtype=u32)
        if steps_len:
            # +1 trash slot: accesses past the last bound (and no-bounds
            # lanes of a mixed batch) scatter there instead of a window
            init["steps"] = jnp.zeros((steps_len + 1,), dtype=jnp.float64)
        return init

    def evict(ln, s, cont):
        """The eviction loop of one lane after ``ln.advance``."""
        c = jax.lax.while_loop(
            ln.econd, lambda c: ln.ebody(c, s, None),
            dict({k: s[k] for k in ev_keys}, cont=cont))
        return dict(s, **{k: c[k] for k in ev_keys})

    def hold(active, new, old):
        """``new`` where the lane is active, else ``old`` (page state
        excepted, see ``page_state``)."""
        return {k: v if k in page_state else jnp.where(active, v, old[k])
                for k, v in new.items()}

    def kernel(*refs):
        rows = dict(zip(in_names, (r[...] for r in refs[:n_inputs])))
        if n_lanes == 1:
            # one lane: its own loop over its accesses, unbatched
            ln = lane({k: v[0] for k, v in rows.items()})
            final = jax.lax.fori_loop(
                0, ln.n, lambda t, s: evict(ln, *ln.advance(t, s, None)),
                init_state())
            out = jax.tree.map(lambda x: x[None], ln.finish(final))
        else:
            # lockstep: one loop over trace positions up to the batch's
            # longest lane; each step advances every lane by one access
            def per_lane(fn):
                return jax.vmap(lambda L, *a: fn(lane(L), *a))

            lane_go = per_lane(lambda ln, c: ln.econd(c))
            lane_evict = per_lane(lambda ln, c, s, go: ln.ebody(c, s, go))

            def lockstep(t, s):
                def advance(ln, s):
                    active = t < ln.n
                    return ln.advance(t, s, active) + (active,)

                new, cont, active = per_lane(advance)(rows, s)
                # evictions in lockstep too, until no lane has a victim
                # left; a lane that is done evicts nothing meanwhile
                c = jax.lax.while_loop(
                    lambda c: jnp.any(lane_go(rows, c)),
                    lambda c: lane_evict(rows, c, new, lane_go(rows, c)),
                    dict({k: new[k] for k in ev_keys}, cont=cont))
                new = dict(new, **{k: c[k] for k in ev_keys})
                return jax.vmap(hold)(active, new, s)

            init = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_lanes,) + x.shape),
                init_state())
            final = jax.lax.fori_loop(0, jnp.max(rows["ip"][:, 0]),
                                      lockstep, init)
            out = per_lane(lambda ln, f: ln.finish(f))(rows, final)
        if steps_len:
            refs[n_inputs][...], refs[n_inputs + 1][...] = out
        else:
            refs[n_inputs][...] = out

    n_stats = len(STAT_FIELDS) + (len(MT_STAT_FIELDS) if mt else 0)
    out_shape = jax.ShapeDtypeStruct((n_lanes, n_stats), jnp.float64)
    if steps_len:
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((n_lanes, steps_len),
                                          jnp.float64)]
    # interpret=True is Pallas's discharge lowering: an XLA program on
    # every platform.  One grid step holds the whole batch: every block
    # is the full (n_lanes, ...) array (see the module docstring,
    # "Lowering")
    call = pl.pallas_call(
        kernel,
        grid=(1,),
        out_shape=out_shape,
        interpret=True,
    )
    return jax.jit(call)


def _lane_shape(request: ReplayRequest) -> Tuple[str, str, int, int]:
    """(family, eviction policy, length, span) of one request's lane.

    A batch may mix eviction policies (the policy is a per-lane value of
    the lane program); the policy stays in the shape so that sorting by
    it keeps one policy's lanes together, and a batch holds as few
    policies as its lanes allow.
    """
    lo, hi = dense_bounds(request.trace, request.prefetcher)
    return (lane_family(request.prefetcher) or "unpackable",
            request.config.eviction,
            len(request.trace.pages), hi - lo)


class PallasReplayBackend(ReplayBackend):
    name = "pallas"

    def is_native(self) -> bool:
        """True on a TPU, where the lanes are a compiled device program;
        elsewhere the same program runs through XLA:CPU and loses to the
        NumPy engine.  A sweep worker process is never native: its parent
        holds the chip (:func:`repro.uvm.replay_core.require_device`), so
        asking JAX here would initialise the accelerator a second time.
        Backend initialisation errors propagate."""
        if device_held_by_parent():
            return False
        import jax
        return jax.default_backend() == "tpu"

    # ------------------------------------------------------------------
    def can_replay(self, request: ReplayRequest) -> bool:
        pf = request.prefetcher
        family = lane_family(pf)
        if family is None:
            return False
        kind = _family_kind(family)
        if request.config.eviction not in EVICTION_POLICIES:
            return False          # unknown policy: legacy raises clearly
        if request.record_timeline:
            return False          # per-transfer timelines stay host-side
        if request.step_bounds is not None:
            # per-step clocks are captured in-kernel (a per-window f64
            # carry keyed by an access->window id stream); malformed or
            # oversized bounds fall back to the host-side backends, whose
            # validation raises the canonical ValueError
            sb = np.asarray(request.step_bounds, dtype=np.int64)
            if (sb.ndim != 1 or sb.size == 0 or sb.size > MAX_LANE_STEPS
                    or np.any(np.diff(sb) < 0) or sb[0] < 0
                    or sb[-1] > len(request.trace.pages)):
                return False
        try:
            # invalid tenancy (quotas without an mt trace / capacity):
            # decline so the host-side backends raise the canonical error
            resolve_tenancy(request.trace, request.config)
        except ValueError:
            return False
        n = len(request.trace.pages)
        if n == 0 or n > _FAMILY_MAX_ACCESSES[kind]:
            return False          # int32 stamp/counter headroom (above)
        if kind == "learned" and len(pf.predicted_pages) < n:
            return False          # decision stream must cover the trace
        if kind == "oracle" and not (0 < pf.lookahead
                                     <= MAX_ORACLE_LOOKAHEAD):
            return False          # window width is a static kernel shape
        lo, hi = dense_bounds(request.trace, pf)
        span = hi - lo
        return lo >= 0 and span <= min(request.max_span_pages,
                                       MAX_LANE_SPAN_PAGES)

    # ------------------------------------------------------------------
    @staticmethod
    def fits_batch(shapes: Sequence[Tuple[str, str, int, int]],
                   shape: Tuple[str, str, int, int]) -> bool:
        """True if a lane of ``shape`` = (family, policy, length, span) —
        the :func:`_lane_shape` of a request — fits a batch that already
        holds lanes of ``shapes`` under the family-homogeneity rule and
        the lane-count, padded state, and padded access budgets.  Lanes
        of different eviction policies share a batch.  The scheduler uses
        this to flush batches incrementally instead of materializing
        whole grids.
        """
        fam, _, t, sp = shape
        if any(f != fam for f, _, _, _ in shapes):
            return False    # never co-bucket families
        n = len(shapes) + 1
        t = max([t] + [s[2] for s in shapes])
        sp = max([sp] + [s[3] for s in shapes])
        return (n <= MAX_LANES_PER_BATCH
                and n * sp <= MAX_BATCH_STATE_PAGES
                and n * t <= MAX_BATCH_ACCESSES)

    def pack_lanes(self, requests: Sequence[ReplayRequest]
                   ) -> List[List[int]]:
        """Group request indices into family-homogeneous lane batches.

        Cells are sorted by (family, policy, length, span) so lanes of
        one batch share a kernel, hold as few eviction policies as they
        can, and pad to similar shapes, then greedily packed under
        :meth:`fits_batch`'s budgets.  Deterministic in the request
        order.
        """
        order = sorted(range(len(requests)),
                       key=lambda i: _lane_shape(requests[i]), reverse=True)
        batches: List[List[int]] = []
        cur: List[int] = []
        cur_shapes: List[Tuple[str, str, int, int]] = []
        for i in order:
            shape = _lane_shape(requests[i])
            if cur and not self.fits_batch(cur_shapes, shape):
                batches.append(cur)
                cur, cur_shapes = [], []
            cur.append(i)
            cur_shapes.append(shape)
        if cur:
            batches.append(cur)
        return batches

    # ------------------------------------------------------------------
    def replay(self, requests: Sequence[ReplayRequest]) -> List[UVMStats]:
        require_device("a pallas lane batch")
        for req in requests:
            if not self.can_replay(req):
                raise ValueError(
                    f"request not packable into pallas lanes "
                    f"({type(req.prefetcher).__name__}); route it through "
                    "the numpy backend")
        # chaos injection site: a "raise" spec here surfaces as a
        # TransientBackendFault, which the dispatch chain and the sweep
        # scheduler re-raise (retry on this backend) instead of degrading
        from repro.uvm import faults
        faults.fire("backend.replay",
                    f"{len(requests)}:{requests[0].trace.name}")
        out: List[UVMStats] = [None] * len(requests)  # type: ignore
        for batch in self.pack_lanes(requests):
            for i, stats in zip(batch,
                                self._replay_batch([requests[i]
                                                    for i in batch])):
                out[i] = stats
        return out

    # ------------------------------------------------------------------
    def _replay_batch(self, requests: Sequence[ReplayRequest]
                      ) -> List[UVMStats]:
        """Replay one family-homogeneous lane batch: pad, launch, unpack.
        Its lanes may run under different eviction policies."""
        import jax

        families = {lane_family(r.prefetcher) for r in requests}
        assert len(families) == 1, \
            f"lane batch must be family-homogeneous, got {families}"
        family = families.pop()
        kind = _family_kind(family)
        lookahead = int(family.split("/")[1]) if kind == "oracle" else 0
        policies = tuple(sorted({r.config.eviction for r in requests}))
        policy = policy_label(policies)
        mixed = len(policies) > 1

        lanes = len(requests)
        obs.count("lane.batches")
        if mixed:
            obs.count("lane.mixed_batches")
        obs.count("lane.lanes", lanes)
        obs.count("lane.accesses", sum(len(r.trace.pages) for r in requests))
        # device loop steps: the lanes advance in lockstep, so a batch
        # runs as many steps as its longest lane has accesses
        obs.count("lane.steps", max(len(r.trace.pages) for r in requests))
        with obs.span("lane.pad", family=family, policy=policy, lanes=lanes):
            shapes = [_lane_shape(r) for r in requests]
            t_max = _bucket(max(t for _, _, t, _ in shapes), 64)
            span = _bucket(max(s for _, _, _, s in shapes), ROOT_PAGES)
            buf_len = max(int(r.config.mshr_entries) for r in requests) + 1
            n_lanes = _bucket(lanes, 1)
            ft_len = 0
            if kind == "oracle":
                ft_len = _bucket(max(len(r.prefetcher.ft_pages)
                                     for r in requests), 64) + lookahead
            step_sizes = [0 if r.step_bounds is None
                          else int(np.asarray(r.step_bounds).size)
                          for r in requests]
            steps_len = (_bucket(max(step_sizes), 64) if any(step_sizes)
                         else 0)
            # mt is a static kernel flag but tenancy stays per-lane dynamic:
            # single-tenant lanes of a mixed batch ride with boundary = IMAX
            # and q0 = -1, which keeps their replay bit-identical (see
            # _lane_replay_fn), so packing needs no tenancy homogeneity
            tenancies = [resolve_tenancy(r.trace, r.config) for r in requests]
            mt = any(t is not None for t in tenancies)

            pages = np.zeros((n_lanes, t_max), dtype=np.int32)
            fparams = np.zeros((n_lanes, _N_FPARAMS), dtype=np.float64)
            # a mixed-policy batch appends each lane's policy index
            iparams = np.full((n_lanes, _N_IPARAMS + mixed), -1,
                              dtype=np.int32)
            iparams[:, 0] = 0                  # padding lanes replay nothing
            iparams[:, 6] = np.iinfo(np.int32).max  # single-tenant boundary
            extra_in: List[np.ndarray] = []
            if kind == "learned":
                preds_in = np.full((n_lanes, t_max), -1, dtype=np.int32)
                extra_in = [preds_in]
            elif kind == "oracle":
                # padded first-touch entries point at the trash slot ``span``
                ft_in = np.full((n_lanes, ft_len), span, dtype=np.int32)
                pos_in = np.zeros((n_lanes, t_max), dtype=np.int32)
                extra_in = [ft_in, pos_in]
            if steps_len:
                sids_in = np.zeros((n_lanes, t_max), dtype=np.int32)
                extra_in = extra_in + [sids_in]
            for l, req in enumerate(requests):
                trace, cfg, pf = req.trace, req.config, req.prefetcher
                pf.reset()
                n = len(trace.pages)
                lo, _ = dense_bounds(trace, pf)
                pages[l, :n] = np.asarray(trace.pages, dtype=np.int64) - lo
                fparams[l] = (
                    cycles_per_access(trace, cfg), cfg.page_transfer_cycles,
                    cfg.far_fault_cycles, cfg.page_table_walk_cycles,
                    cfg.pcie_latency_cycles, cfg.prefetch_overhead_cycles,
                    pf.extra_latency_cycles, cfg.page_size)
                has_block = (type(pf) is BlockPrefetcher
                             or (type(pf) is LearnedPrefetcher
                                 and pf.prefetch_block))
                iparams[l, :4] = (
                    n,
                    -1 if cfg.device_pages is None else int(cfg.device_pages),
                    int(cfg.mshr_entries),
                    1 if has_block else 0)
                # lane lo mod 2^32 (int32 bit pattern): random-policy draws
                # hash the absolute page id, identical across backends
                iparams[l, 5] = np.array(lo & 0xFFFFFFFF,
                                         dtype=np.uint32).astype(np.int32)
                tn = tenancies[l]
                if tn is not None:
                    # dense boundary: may fall outside [0, span) when a trace
                    # slice only touches one tenant's region — the compares
                    # stay correct either way (all-0 / all-1 lanes)
                    iparams[l, 6] = int(tn.boundary) - lo
                    if tn.split:
                        iparams[l, 7] = int(tn.quotas[0])
                        iparams[l, 8] = int(tn.quotas[1])
                if mixed:
                    iparams[l, _N_IPARAMS] = policies.index(cfg.eviction)
                if kind == "learned":
                    pr = np.asarray(pf.predicted_pages, dtype=np.int64)[:n]
                    preds_in[l, :n] = np.where(pr >= 0, pr - lo, -1)
                elif kind == "oracle":
                    ftp = np.asarray(pf.ft_pages, dtype=np.int64) - lo
                    ft_in[l, :len(ftp)] = ftp
                    # the stream position is a pure function of the access
                    # index (it only ever advances): precompute it host-side
                    pos_in[l, :n] = np.searchsorted(
                        pf.ft_index, np.arange(n), side="right")
                    iparams[l, 4] = len(ftp)
                if steps_len and req.step_bounds is not None:
                    sb = np.asarray(req.step_bounds, dtype=np.int64)
                    # window id per access; accesses past the last bound go
                    # to the trash slot ``steps_len``
                    sid = np.searchsorted(sb, np.arange(n), side="right")
                    sids_in[l, :n] = np.where(sid >= sb.size, steps_len,
                                              sid).astype(np.int32)

        obs.count("lane.padded_lanes", n_lanes - lanes)
        compile_cache.enable()
        with obs.span("lane.dispatch", family=family, policy=policy), \
                jax.enable_x64(True):
            fn = _lane_replay_fn(kind, policies, n_lanes, t_max, span,
                                 buf_len, ft_len, lookahead, steps_len, mt)
            raw = fn(pages, *extra_in, fparams, iparams)
        with obs.span("lane.fetch"):
            if steps_len:
                raw, raw_steps = (np.asarray(raw[0]), np.asarray(raw[1]))
            else:
                raw = np.asarray(raw)

        with obs.span("lane.unpack"):
            out = []
            for l, req in enumerate(requests):
                row = raw[l]
                stats = UVMStats(
                    name=req.trace.name,
                    prefetcher=req.prefetcher.name,
                    n_accesses=len(req.trace.pages),
                    n_instructions=req.trace.n_instructions,
                    cycles=float(row[0]),
                    hits=int(row[1]),
                    late=int(row[2]),
                    faults=int(row[3]),
                    prefetch_issued=int(row[4]),
                    prefetch_used=int(row[5]),
                    pages_migrated=int(row[6]),
                    pages_evicted=int(row[7]),
                    pcie_bytes=float(row[8]),
                    zero_copy_bytes=0.0,
                    timeline=None,
                    eviction=req.config.eviction,
                )
                stats.backend = self.name
                if tenancies[l] is not None:
                    th0 = int(row[len(STAT_FIELDS)])
                    stats.tenant_hits = (th0, stats.hits - th0)
                    stats.tenant_accesses = _tenant_accesses(
                        req.trace.pages, tenancies[l])
                if steps_len and req.step_bounds is not None:
                    stats.step_clocks = _fill_step_clocks(
                        np.asarray(req.step_bounds, dtype=np.int64),
                        raw_steps[l])
                out.append(stats)
        return out


def _fill_step_clocks(bounds: np.ndarray, lane_steps: np.ndarray
                      ) -> np.ndarray:
    """Kernel per-window clock maxima -> ``UVMStats.step_clocks``.

    The kernel only writes windows that own at least one access, so empty
    windows (duplicate bounds) forward-fill from the previous non-empty
    window and leading empty windows end at clock 0.0 — the exact
    semantics of the legacy/numpy recording loop (``replay_chunked``),
    which writes the then-current clock as it crosses duplicate bounds.
    """
    n_steps = bounds.size
    vals = np.asarray(lane_steps[:n_steps], dtype=np.float64)
    sizes = np.diff(np.concatenate([[0], bounds]))
    idx = np.where(sizes > 0, np.arange(n_steps), -1)
    idx = np.maximum.accumulate(idx)
    return np.where(idx >= 0, vals[np.maximum(idx, 0)], 0.0)

