"""Readings that the comparison's limits are set from.

    python3 bench/control.py --workload atax.replay --seeds 11,12,13 \
        --control-seeds 11,12,13 [--out readings.json]

For each of ``--seeds`` it builds the cell's trace, runs one grid of the
cell through the sweep on the chip (``harness.run_window``, after one
warm-up grid) and compares its rows with the plain reference: the lower
readings, from the program.  For each of ``--control-seeds`` it puts the
control in the program's place (``compare.control_checks``: the
reference's rows replayed with a float32 timing state, and each family's
own control beside the three numbers of the replay): the upper
readings.  A grid whose predictor the program trains gets a fresh one,
as in the benchmark's window, and the control of a seed is given what
the chip trained on it.  Prints one JSON line per seed and a summary
(largest lower and smallest upper reading of every number, beside the
cell's limit where it has one).  The benchmark's own runs never run
this.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import compare, harness

    cell = harness.load_cell(args.workload)
    harness.require_chips(cell.chips)
    from repro import compile_cache
    from repro.uvm.sweep import SweepCell, load_trace

    compile_cache.enable()
    compiles = harness.CompileCounter()
    conf = cell.config
    lines, trained = [], {}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",") if s):
        sweep = [SweepCell(**c) for c in harness.grid(cell, seed)]
        trace = load_trace(conf["bench"], conf["scale"], seed,
                           conf["window"])
        harness.check_pins(trace, conf)
        if i == 0:
            harness.run_window([(seed, sweep)], 0.0, compiles)  # compiles
        win = harness.run_window([(seed, sweep)], 0.0, compiles)
        trained[seed] = win.trained[0]
        checks = compare.check_window(
            conf, {seed: trace}, [(seed, win.grids[0], win.trained[0])],
            {seed: sweep})
        checks["failed_rows"] = harness.failed_rows(win.grids[0])
        lines.append({"side": "program", "seed": seed, "checks": checks,
                      "grid_s": win.seconds, "compiles": win.compiles})
        print(json.dumps(lines[-1]), flush=True)
    for seed in (int(s) for s in args.control_seeds.split(",") if s):
        sweep = [SweepCell(**c) for c in harness.grid(cell, seed)]
        if harness.trains(sweep) and seed not in trained:
            trained[seed] = harness.run_window([(seed, sweep)], 0.0,
                                               compiles).trained[0]
        lines.append({"side": "control", "seed": seed,
                      "checks": compare.control_checks(
                          conf, seed, sweep, trained.get(seed))})
        print(json.dumps(lines[-1]), flush=True)
    summary = {}
    limits = cell.workload["limits"]
    for k in sorted({k for ln in lines for k in ln["checks"]}):
        lo = [ln["checks"][k] for ln in lines
              if ln["side"] == "program" and k in ln["checks"]]
        up = [ln["checks"][k] for ln in lines
              if ln["side"] == "control" and k in ln["checks"]]
        summary[k] = {"lower": max(lo) if lo else None,
                      "upper": min(up) if up else None,
                      "limit": limits.get(k)}
    print(json.dumps({"workload": cell.name, "summary": summary}),
          flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": cell.name, "lines": lines,
                       "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
