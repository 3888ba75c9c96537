#!/usr/bin/env bash
# CI gate: tier-1 tests + the UVM golden-equivalence and sweep suites.
#
#   bash scripts/ci_check.sh
#
# Installs the test dependencies (hypothesis enables the property-based
# suites; without it they degrade to skips, so an offline install failure is
# tolerated but surfaced).
set -euo pipefail
cd "$(dirname "$0")/.."

if ! python -c "import hypothesis" 2>/dev/null; then
    echo "[ci] installing test dependencies (hypothesis, pytest)"
    python -m pip install -q "hypothesis>=6" "pytest>=7" \
        || echo "[ci] WARNING: could not install hypothesis (offline?);" \
                "property-based suites will run as skips"
fi

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "[ci] tier-1: full test suite (golden/sweep/backends/predcache/faults gated separately)"
python -m pytest -x -q --ignore=tests/test_uvm_golden.py \
    --ignore=tests/test_sweep.py --ignore=tests/test_predcache.py \
    --ignore=tests/test_backends.py --ignore=tests/test_faults.py

echo "[ci] replay backends: golden suite against numpy AND pallas lanes"
echo "[ci] (all five prefetcher families x all eviction policies),"
echo "[ci] backend contract + lane-packing property suite, cross-backend"
echo "[ci] differential fuzzer (policy axis included), sweep, scenarios,"
echo "[ci] predcache + fault plane (pallas runs in interpret mode,"
echo "[ci] CPU platform pinned)"
JAX_PLATFORMS=cpu python -m pytest -q tests/test_uvm_golden.py \
    tests/test_backends.py tests/test_differential.py \
    tests/test_scenarios.py tests/test_sweep.py tests/test_predcache.py \
    tests/test_faults.py

echo "[ci] scenario-matrix smoke: oversub-smoke (2 benchmarks x 2 ratios"
echo "[ci] x all eviction policies, < 100k total accesses) through the"
echo "[ci] pallas lanes in interpret mode; every row must record"
echo "[ci] backend=pallas and its eviction policy"
SCN_OUT="$(mktemp -d "${TMPDIR:-/tmp}/ci_scenario_smoke.XXXXXX")"
JAX_PLATFORMS=cpu python -m repro.uvm.sweep --scenario oversub-smoke \
    --backend pallas --out "$SCN_OUT"
python - "$SCN_OUT" <<'PYEOF'
import json, sys
rows = json.load(open(sys.argv[1] + "/results.json"))["rows"]
assert len(rows) == 24, f"scenario smoke expanded {len(rows)} cells, not 24"
bad = [r for r in rows if r["backend"] != "pallas"]
assert not bad, f"{len(bad)} smoke cells fell off the pallas lanes"
policies = {r["eviction"] for r in rows}
assert policies == {"lru", "random", "hotcold"}, policies
assert all(r["scenario"] == "oversub-smoke" for r in rows)
assert all(r["pages_evicted"] > 0 for r in rows
           if r["device_frac"] == 0.5 and r["prefetcher"] == "none")
print(f"[ci] scenario smoke ok: {len(rows)} rows, policies {sorted(policies)}")
PYEOF
rm -rf "$SCN_OUT"

echo "[ci] serve-traffic smoke: serve-smoke (PagedKVStore-derived traces:"
echo "[ci] 2 serve workloads x 2 ratios x all eviction policies x"
echo "[ci] none/block) through the pallas lanes in interpret mode; every"
echo "[ci] row must record its backend, policy, and ordered latency"
echo "[ci] percentiles (decode p50/p95/p99 + TTFT p50/p95/p99)"
SRV_OUT="$(mktemp -d "${TMPDIR:-/tmp}/ci_serve_smoke.XXXXXX")"
JAX_PLATFORMS=cpu python -m repro.uvm.sweep --scenario serve-smoke \
    --backend pallas --out "$SRV_OUT"
python - "$SRV_OUT" <<'PYEOF'
import json, sys
rows = json.load(open(sys.argv[1] + "/results.json"))["rows"]
assert len(rows) == 24, f"serve smoke expanded {len(rows)} cells, not 24"
bad = [r for r in rows if r["backend"] != "pallas"]
assert not bad, f"{len(bad)} serve cells fell off the pallas lanes"
policies = {r["eviction"] for r in rows}
assert policies == {"lru", "random", "hotcold"}, policies
assert all(r["scenario"] == "serve-smoke" for r in rows)
lat = ("decode_lat_p50_us", "decode_lat_p95_us", "decode_lat_p99_us",
       "ttft_p50_us", "ttft_p95_us", "ttft_p99_us")
for r in rows:
    for f in lat:
        assert isinstance(r[f], float) and r[f] > 0.0, (f, r[f], r["bench"])
    assert (r["decode_lat_p50_us"] <= r["decode_lat_p95_us"]
            <= r["decode_lat_p99_us"]), r["bench"]
    assert r["ttft_p50_us"] <= r["ttft_p95_us"] <= r["ttft_p99_us"], r["bench"]
# lane rows must take the in-kernel step-clock path: a "side-pass" here
# means the pre-PR-8 double replay (lane kernel + full NumPy shadow pass
# per serve row) silently came back
side = [r["bench"] for r in rows if r.get("slo_source") != "kernel"]
assert not side, f"lane rows fell back to the side-pass SLO path: {side}"
print(f"[ci] serve smoke ok: {len(rows)} rows, policies {sorted(policies)}, "
      f"all SLO columns from in-kernel step clocks")
PYEOF
rm -rf "$SRV_OUT"

echo "[ci] multi-tenant smoke: mt-smoke (1 interleaved bench pair x 2"
echo "[ci] oversubscribed ratios x 3 capacity splits (shared / hard 50-50"
echo "[ci] / 40-40 + spill) x all eviction policies x none/tree) through"
echo "[ci] the pallas lanes in interpret mode; every row must record"
echo "[ci] tenants, its capacity split, both per-tenant hit rates, and"
echo "[ci] the interference slowdown vs the tenants' solo replays"
MT_OUT="$(mktemp -d "${TMPDIR:-/tmp}/ci_mt_smoke.XXXXXX")"
JAX_PLATFORMS=cpu python -m repro.uvm.sweep --scenario mt-smoke \
    --backend pallas --out "$MT_OUT"
python - "$MT_OUT" <<'PYEOF'
import json, sys
rows = json.load(open(sys.argv[1] + "/results.json"))["rows"]
assert len(rows) == 36, f"mt smoke expanded {len(rows)} cells, not 36"
bad = [r for r in rows if r["backend"] != "pallas"]
assert not bad, f"{len(bad)} mt cells fell off the pallas lanes"
policies = {r["eviction"] for r in rows}
assert policies == {"lru", "random", "hotcold"}, policies
splits = {r["capacity_split"] for r in rows}
assert splits == {"shared", "0.5/0.5", "0.4/0.4"}, splits
for r in rows:
    assert r["scenario"] == "mt-smoke"
    assert r["tenants"] == 2, r["tenants"]
    # hit rates may legitimately hit 0.0 (streaming tenant under demand
    # paging); slowdowns are ratios of positive cycle counts, never 0
    for f in ("hit_rate_t0", "hit_rate_t1"):
        assert isinstance(r[f], float) and r[f] >= 0.0, (f, r.get(f))
    for f in ("slowdown_t0", "slowdown_t1", "interference_slowdown"):
        assert isinstance(r[f], float) and r[f] > 0.0, (f, r.get(f))
    assert abs(r["interference_slowdown"]
               - max(r["slowdown_t0"], r["slowdown_t1"])) < 1e-12
# the quota must do visible work: under pressure, the hard 50/50 split
# lifts tenant 0's hit rate over shared contention for the same cell
key = lambda r: (r["device_frac"], r["eviction"], r["prefetcher"])
shared = {key(r): r for r in rows if r["capacity_split"] == "shared"}
lifted = sum(1 for r in rows if r["capacity_split"] == "0.5/0.5"
             and r["hit_rate_t0"] > shared[key(r)]["hit_rate_t0"])
assert lifted > 0, "no quota cell improved tenant 0 over shared capacity"
print(f"[ci] mt smoke ok: {len(rows)} rows, splits {sorted(splits)}, "
      f"{lifted} quota cells lifted the protected tenant")
PYEOF
rm -rf "$MT_OUT"

echo "[ci] chaos-smoke: the chaos-smoke scenario fault-free and under the"
echo "[ci] bounded kill+corrupt+raise plan (SIGKILLed drivers restarted,"
echo "[ci] torn/corrupted artifacts quarantined + regenerated); the final"
echo "[ci] grid must be byte-identical to the baseline with an empty"
echo "[ci] quarantine manifest"
CHAOS_OUT="$(mktemp -d "${TMPDIR:-/tmp}/ci_chaos_smoke.XXXXXX")"
JAX_PLATFORMS=cpu python -m repro.uvm.faults --scenario chaos-smoke \
    --backend pallas --out "$CHAOS_OUT" > "$CHAOS_OUT/report.json"
python - "$CHAOS_OUT" <<'PYEOF'
import json, sys
report = json.loads(open(sys.argv[1] + "/report.json").read()
                    .strip().splitlines()[-1])
assert report["cells"] == 8, report
assert report["faults_fired"] > 0, \
    f"chaos smoke injected no faults - the check is vacuous: {report}"
manifest = json.load(open(sys.argv[1] + "/chaos/quarantine.json"))
assert manifest["cells"] == [], manifest
print(f"[ci] chaos smoke ok: {report['cells']} cells byte-identical after "
      f"{report['faults_fired']} faults, {report['restarts']} restarts, "
      f"{report['retries']} retries")
PYEOF
rm -rf "$CHAOS_OUT"

echo "[ci] transformer-smoke: the model_family axis (simplified vs the"
echo "[ci] reference Transformer learned cells) x the adaptive eviction"
echo "[ci] pseudo-policy through the pallas lanes in interpret mode; every"
echo "[ci] row must record its model_family and the concrete policy the"
echo "[ci] adaptive selector resolved to (pinned via ADAPTIVE_selector.json)"
TF_OUT="$(mktemp -d "${TMPDIR:-/tmp}/ci_tf_smoke.XXXXXX")"
REPRO_ADAPTIVE_TABLE=ADAPTIVE_selector.json JAX_PLATFORMS=cpu \
    python -m repro.uvm.sweep --scenario transformer-smoke \
    --backend pallas --out "$TF_OUT"
python - "$TF_OUT" ADAPTIVE_selector.json <<'PYEOF'
import json, sys
rows = json.load(open(sys.argv[1] + "/results.json"))["rows"]
assert len(rows) == 4, f"transformer smoke expanded {len(rows)} cells, not 4"
bad = [r for r in rows if r["backend"] != "pallas"]
assert not bad, f"{len(bad)} transformer cells fell off the pallas lanes"
fams = {r["model_family"] for r in rows}
assert fams == {"simplified", "transformer"}, fams
# the adaptive pseudo-policy may never leak into result rows: each cell
# records the concrete policy the selector resolved to for its benchmark
leaked = [r["bench"] for r in rows if r["eviction"] == "adaptive"]
assert not leaked, f"rows recorded the adaptive literal: {leaked}"
# data-driven against the committed selector table (re-recorded via
# scripts/record_adaptive_selector.py): each bench must resolve to
# exactly its table entry
selector = json.load(open(sys.argv[2]))["selector"]
by_bench = {}
for r in rows:
    by_bench.setdefault(r["bench"], set()).add(r["eviction"])
want = {b: {selector[b]} for b in by_bench}
assert by_bench == want, f"{by_bench} != selector picks {want}"
print(f"[ci] transformer smoke ok: {len(rows)} rows, families {sorted(fams)}, "
      f"adaptive resolved " + str({b: sorted(p) for b, p in by_bench.items()}))
PYEOF
rm -rf "$TF_OUT"

echo "[ci] counter trajectory: mt_bench vs the committed BENCH_mt.json"
echo "[ci] (row-key schema drift and counter drift always fail; speed is"
echo "[ci] measured on the chip by bench/run.py, not here)"
BENCH_TMP="$(mktemp -d "${TMPDIR:-/tmp}/ci_bench.XXXXXX")"
# multi-tenant trajectory: the per-tenant hit rates and interference
# slowdowns are counter_* fields, so any accounting drift fails exactly
# (backend-agnostic — the backends are bit-equal on mt cells)
REPRO_SWEEP_CACHE_DIR="$BENCH_TMP/mt_cache" JAX_PLATFORMS=cpu \
    python -m benchmarks.mt_bench --emit-json "$BENCH_TMP/mt.json"
python scripts/check_bench.py BENCH_mt.json "$BENCH_TMP/mt.json"

echo "[ci] predictor families: simplified-vs-Transformer accuracy benchmark"
echo "[ci] (quick smoke set, trained fresh: benchmarks/cache is gitignored)"
echo "[ci] vs the committed BENCH_families.json schema; the reference"
echo "[ci] Transformer must reach the simplified predictor's accuracy on"
echo "[ci] every smoke bench"
REPRO_BENCH_QUICK=1 JAX_PLATFORMS=cpu python -m benchmarks.family_accuracy \
    --emit-json "$BENCH_TMP/families.json"
python scripts/check_bench.py BENCH_families.json "$BENCH_TMP/families.json"
python - "$BENCH_TMP/families.json" <<'PYEOF'
import json, sys
rows = json.load(open(sys.argv[1]))["rows"]
by = {(r["bench"], r["model_family"]): r for r in rows}
benches = sorted({r["bench"] for r in rows})
for b in benches:
    tf, simp = by[(b, "transformer")], by[(b, "simplified")]
    assert tf["top1"] >= simp["top1"] - 1e-9, \
        f"transformer under the simplified bar on {b}: " \
        f"{tf['top1']:.4f} < {simp['top1']:.4f}"
print("[ci] family accuracy ok: transformer >= simplified on "
      + ",".join(benches))
PYEOF
rm -rf "$BENCH_TMP"

echo "[ci] OK"
