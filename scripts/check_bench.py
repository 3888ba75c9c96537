"""Gate a fresh BENCH_*.json emission against its committed baseline.

    python scripts/check_bench.py BENCH_mt.json /tmp/new_mt.json

Two rule classes, applied per row (rows are keyed by their ``name``):

* **Schema** — the set of row names and the key set of every row must
  match the baseline exactly.  Missing or extra rows/keys fail the run
  unconditionally: schema drift in a trajectory file silently breaks
  every later diff, so it is never tolerated.
* **Counters** — ``counter_*`` fields must be identical.  Counter drift
  is a correctness bug, not a perf regression; no tolerance applies.

Speed is not gated here: the benchmark (``bench/run.py``) measures it on
the chip.
"""
from __future__ import annotations

import json
import os
import sys
from typing import List


def check(baseline_path: str, current_path: str) -> List[str]:
    with open(baseline_path) as f:
        base_doc = json.load(f)
    with open(current_path) as f:
        cur_doc = json.load(f)
    errors: List[str] = []
    base = {r["name"]: r for r in base_doc["rows"]}
    cur = {r.get("name"): r for r in cur_doc["rows"]}

    missing = sorted(set(base) - set(cur))
    extra = sorted(set(cur) - set(base))
    if missing:
        errors.append(f"missing rows (in baseline, not in emission): "
                      f"{missing}")
    if extra:
        errors.append(f"extra rows (in emission, not in baseline): {extra}")

    for name in sorted(set(base) & set(cur)):
        b, c = base[name], cur[name]
        if set(b) != set(c):
            errors.append(f"{name}: row key drift — missing "
                          f"{sorted(set(b) - set(c))}, extra "
                          f"{sorted(set(c) - set(b))}")
            continue
        for field in sorted(b):
            if field.startswith("counter_") and b[field] != c[field]:
                errors.append(f"{name}: {field} drifted "
                              f"{b[field]!r} -> {c[field]!r} (always fatal)")
    return errors


def main(argv: List[str]) -> int:
    if len(argv) != 3 or argv[1] in ("-h", "--help"):
        print(__doc__)
        return 2
    errors = check(argv[1], argv[2])
    tag = os.path.basename(argv[1])
    if errors:
        for e in errors:
            print(f"[check_bench] {tag}: FAIL: {e}")
        return 1
    print(f"[check_bench] {tag}: ok")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
