"""The benchmark's run: set-up, the timed window, correctness, the result.

One process on one chip.  Everything that belongs to one cell is data
found by name: ``BENCHMARK.json`` names the cell, its configuration file
(``bench/configs/<config>.json``) and its traffic file
(``bench/workloads/<cell>.json``: the traces, the grid axes, the limits
of the comparison and the size a CPU test holds); each per-layer metric
is a reader in ``bench/metrics/<metric>.py``.  The plain reference finds
the configuration's trace generator in
``bench/reference/traces/<config>.py`` and each prefetcher family of the
grid in ``bench/reference/prefetchers/<prefetcher>.py``.

The window drives the sweep through its own entry point,
``repro.uvm.sweep.run_sweep(cells, workers=1)``, one whole grid after
another (closed loop, the way a researcher reruns a sweep).  A pass
replays the grid once on each trace of the cell's traffic file
(``trace_seeds``), in the order ``--seed`` draws; passes follow each
other until ``--seconds`` have passed, and the pass that crosses the
mark is finished and counted.  So every run does the same work, whatever
its seed, and the window's work and its time end at the same lane batch
boundary.

A grid that holds a prefetcher family whose predictor the program trains
(``TRAINED`` in the family's module, ``bench/reference/family.py``)
starts from a fresh predictor: the program's in-process prediction memo
is dropped before it, so the grid trains and infers as a researcher's
sweep does when it meets a trace for the first time.  The window replays
fixed traces only so that every seed does the same work; without the
drop, every grid after a trace's first would reuse the memo and time no
predictor.  Inside a grid the program's own memo works as it does for a
researcher: cells that share a (trace, model) and prepare one after
another train once.  While such a grid runs the harness keeps what the
program trained (:func:`recording_predictors`) and hands it to the
comparison.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import os
import resource
import shutil
import tempfile
import time
from typing import Callable, Dict, List, Optional

from bench.modules import Refused, load_module

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: JAX's event for one XLA compilation or persistent-cache load
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: JAX's event for one program loaded from the persistent cache
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass
class Cell:
    """One benchmark cell, resolved from its files."""

    name: str
    chips: int
    config: Dict
    workload: Dict
    per_layer: List[Dict]          # BENCHMARK.json entries that apply here
    end_to_end: List[Dict]
    backend: str = "auto"


def _read_json(path: str) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise Refused(f"cannot read {os.path.relpath(path, ROOT)}: {e}")


def load_cell(name: str, root: str = ROOT) -> Cell:
    """Resolve cell ``name`` from ``BENCHMARK.json`` and its files."""
    bm = _read_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    return cell_from_files(name, entry["traffic"], entry["config"],
                           int(entry["chips"]), bm, root)


def cell_from_files(name: str, traffic: str, config: str, chips: int,
                    bm: Dict, root: str = ROOT) -> Cell:
    """A cell from its traffic and configuration files, with the
    metrics of ``bm`` (a ``BENCHMARK.json``) that apply to it."""
    conf = next(c for c in bm["configs"] if c["name"] == config)
    workload = _read_json(os.path.join(root, "bench", "workloads",
                                       f"{traffic}.json"))
    if workload["config"] != config:
        raise Refused(f"{name}: traffic file names config "
                      f"{workload['config']!r}, BENCHMARK.json {config!r}")

    def applies(m: Dict) -> bool:
        return name in m.get("workloads", [name])

    cell = Cell(name=name, chips=chips,
                config=_read_json(os.path.join(root, conf["file"])),
                workload=workload,
                per_layer=[m for m in bm["per_layer"] if applies(m)],
                end_to_end=[m for m in bm["end_to_end"] if applies(m)])
    resolve_references(cell, f"bench/workloads/{traffic}.json")
    return cell


def resolve_references(cell: Cell, traffic_file: str) -> None:
    """Load the reference modules the cell's comparison needs, so that a
    missing one is refused before the run starts: the configuration's
    trace generator, the family of every prefetcher of its grid and what
    each family's comparison of a sweep cell loads.  A limit of the
    traffic file that names no number the comparison or a family of the
    grid reports is refused too."""
    from bench import compare
    from bench.reference import family, tracegen

    tracegen.generator(cell.config["name"])
    sweep = grid(cell, 0)
    for c in sweep:
        resolve = getattr(family.load(c["prefetcher"]), "resolve", None)
        if resolve is not None:
            resolve(c)
    known = compare.check_kinds(c["prefetcher"] for c in sweep)
    unknown = sorted(set(cell.workload["limits"]) - set(known))
    if unknown:
        raise Refused(f"{traffic_file}: limits {unknown} name no number "
                      f"that the comparison or a family of the grid "
                      f"reports ({sorted(known)})")


def load_reader(metric: str) -> Callable:
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    return load_module(os.path.join(BENCH_DIR, "metrics"), metric,
                       "reader of the metric").read


def grid(cell: Cell, seed: int) -> List[Dict]:
    """The cell's sweep cells: the product of its grid axes, in file order."""
    axes = cell.workload["grid"]
    fixed = dict(bench=cell.config["bench"], scale=cell.config["scale"],
                 window=cell.config["window"], seed=int(seed),
                 backend=cell.backend)
    fixed.update(cell.workload.get("fixed", {}))
    keys = list(axes)
    return [dict(fixed, **dict(zip(keys, vals)))
            for vals in itertools.product(*(axes[k] for k in keys))]


def trace_order(cell: Cell, seed: int) -> List[int]:
    """The seeds of the traces one pass replays: the traffic file's
    ``trace_seeds``, in an order drawn from ``seed``.  The traces are the
    same for every seed, since the work a trace asks of the lanes (its
    faults and evictions) depends on its seed."""
    import numpy as np

    pool = [int(s) for s in cell.workload["trace_seeds"]]
    rng = np.random.default_rng(seed % 2 ** 64)
    return [pool[i] for i in rng.permutation(len(pool))]


def check_pins(trace, config: Dict) -> None:
    """Refuse a trace whose size moved from the configuration's pins."""
    pins = config["pins"]
    got = {"n_accesses": len(trace.accesses),
           "n_instructions": int(trace.n_instructions)}
    bad = {k: (got[k], v) for k, v in pins.items() if got[k] != v}
    if bad:
        raise Refused(f"trace of {config['name']} moved from its pins "
                      f"(got, pinned): {bad}")


def require_chips(chips: int):
    """The devices, or :class:`Refused` when there is no TPU or too few."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's first device is {devs[0].platform!r} "
                      f"({devs[0].device_kind}); the benchmark runs only "
                      "on the chip")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads, from JAX's
    own monitoring events, since :meth:`reset`."""

    def __init__(self) -> None:
        import jax.monitoring

        self.n = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, name: str, _secs: float, **_kw) -> None:
        if name == COMPILE_EVENT:
            self.n += 1

    def _on_event(self, name: str, **_kw) -> None:
        if name == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def reset(self) -> None:
        self.n = 0
        self.cache_hits = 0


def trains(sweep_cells) -> bool:
    """Whether a grid holds a family whose predictor the program trains."""
    from bench.reference import family

    return any(family.trained(c.prefetcher) for c in sweep_cells)


@contextlib.contextmanager
def recording_predictors(sink: List):
    """While open, every predictor service the program fits and asks for
    its trace's predictions adds ``(service, predictions, confidences,
    training)`` to ``sink``, from the program's own objects: the service
    holds what it trained, the predictions are what the lanes are given,
    the confidences are the top-1 softmax probabilities that its gate
    read, one per window, in the order it inferred them, and the training
    is what its train step was given and gave back (:func:`_recorded_step`).
    A service fits and infers on one thread, and several may run at
    once."""
    import threading

    import numpy as np
    from repro.core import service as svc_mod
    from repro.core import train as train_mod

    predict = svc_mod.PredictorService.predict_trace
    cls_conf = svc_mod.predict_cls_conf
    make_step = train_mod.make_train_step
    local = threading.local()

    def recorded_make_step(*args, **kwargs):
        opt, step_fn = make_step(*args, **kwargs)
        local.training = {"steps_run": 0, "batches": [], "losses": []}
        return opt, _recorded_step(step_fn, local.training)

    def recorded_cls_conf(*args, **kwargs):
        cls, conf = cls_conf(*args, **kwargs)
        if getattr(local, "conf", None) is not None:
            local.conf.append(np.asarray(conf, np.float32))
        return cls, conf

    def recorded(self, *args, **kwargs):
        local.conf = []
        try:
            preds = predict(self, *args, **kwargs)
            conf = local.conf
        finally:
            local.conf = None
        training, local.training = getattr(local, "training", None), None
        sink.append((self, preds, np.concatenate(conf) if conf
                     else np.zeros(0, np.float32), training))
        return preds

    svc_mod.PredictorService.predict_trace = recorded
    svc_mod.predict_cls_conf = recorded_cls_conf
    train_mod.make_train_step = recorded_make_step
    try:
        yield
    finally:
        svc_mod.PredictorService.predict_trace = predict
        svc_mod.predict_cls_conf = cls_conf
        train_mod.make_train_step = make_step


def _recorded_step(step_fn, log: Dict):
    """``step_fn`` that counts its calls in ``log`` and keeps, of the
    first steps the reference follows, what they were given and gave
    back: the parameters the first started from, each batch and loss,
    the optimizer's state after the first and the parameters after the
    last.  It keeps the device's arrays and reads none back, so the
    window waits for nothing; :func:`trained_record` reads them once the
    window has closed."""
    from bench.reference import predictor_training

    def step(params, opt_state, x, y, step_i):
        out = step_fn(params, opt_state, x, y, step_i)
        k = log["steps_run"]
        if k < predictor_training.STEPS:
            if k == 0:
                log["init"], log["state"] = params, out[1]
            log["batches"].append((x, y))
            log["losses"].append(out[2])
            log["params"] = out[0]
        log["steps_run"] = k + 1
        return out
    return step


def trained_record(service, preds, conf, training) -> Dict:
    """What the comparison gets of one trained predictor, as plain dicts
    and NumPy arrays: the trace's content key, the model family, the
    resolved configuration, the trained parameters (float32), the
    predictions and the top-1 confidences of its windows, and its
    training: the steps it ran, the parameters it started from, the
    batches and losses of the first steps, the optimizer's first moment
    after one step and the parameters after the last of them (None where
    the train step was not seen)."""
    import jax
    import numpy as np
    from repro.uvm import predcache

    f32 = lambda tree: jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32), tree)
    cfg = dataclasses.asdict(service.result.cfg)
    cfg["features"] = list(cfg["features"])
    rec = {"trace": predcache.trace_content_key(service.trace),
           "model_family": service.model_family, "config": cfg,
           "params": f32(service.result.params),
           "preds": np.asarray(preds, np.int64), "conf": conf,
           "training": None}
    if training is not None and "init" in training:
        rec["training"] = {
            "steps_run": training["steps_run"],
            "init": f32(training["init"]),
            "batches": [(np.asarray(x, np.int32), np.asarray(y, np.int64))
                        for x, y in training["batches"]],
            "losses": [float(v) for v in training["losses"]],
            "first_moment": f32(training["state"].mu),
            "params": f32(training["params"])}
    return rec


def by_family(records: List[Dict]) -> Dict[str, Optional[Dict]]:
    """A grid's records by model family.  Where the program trained one
    family more than once in a grid (each cell that prepares at the same
    time as another misses the memo), the records have to be the same
    to be anyone's: otherwise the family's rows cannot be matched to the
    one they consumed and get None, which the comparison counts as a
    mismatch."""
    import jax
    import numpy as np

    def same(a: Dict, b: Dict) -> bool:
        la, lb = (jax.tree_util.tree_leaves(r["params"]) for r in (a, b))
        ta, tb = a["training"] or {}, b["training"] or {}
        return (a["trace"] == b["trace"] and a["config"] == b["config"]
                and ta.get("steps_run") == tb.get("steps_run")
                and ta.get("losses") == tb.get("losses")
                and np.array_equal(a["preds"], b["preds"])
                and np.array_equal(a["conf"], b["conf"])
                and len(la) == len(lb)
                and all(np.array_equal(x, y) for x, y in zip(la, lb)))

    out: Dict[str, Optional[Dict]] = {}
    for rec in records:
        fam = rec["model_family"]
        if fam not in out:
            out[fam] = rec
        elif out[fam] is not None and not same(out[fam], rec):
            out[fam] = None
    return out


@dataclasses.dataclass
class Window:
    grids: List[List[Dict]]       # the rows of each finished grid
    #: what the chip trained in each grid, by model family (empty where
    #: the grid trains nothing; see :func:`by_family`)
    trained: List[Dict[str, Optional[Dict]]]
    trace_seeds: List[int]        # the trace each grid replayed
    seconds: float                # window start to the end of the last grid
    compiles: int                 # compilations inside the window
    grid_seconds: List[float]     # host seconds of each grid
    grid_cpu_seconds: List[float]  # the process's CPU seconds in each grid


def run_window(one_pass, seconds: float, compiles: CompileCounter,
               trace_dir: Optional[str] = None) -> Window:
    """Whole passes back to back until ``seconds`` have passed; a pass is
    one grid per ``(trace seed, sweep cells)`` of ``one_pass``.  With
    ``trace_dir`` the window runs under the profiler."""
    import jax
    from repro.uvm.sweep import run_sweep

    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    from repro.uvm import predcache

    grids, seeds, grid_s, grid_cpu, fitted = [], [], [], [], []
    trained_grid = {ts: trains(cells) for ts, cells in one_pass}
    compiles.reset()
    t0 = time.perf_counter()
    try:
        while True:
            for trace_seed, sweep_cells in one_pass:
                sink: List = []
                if trained_grid[trace_seed]:
                    predcache.clear_memo()
                with (recording_predictors(sink) if trained_grid[trace_seed]
                      else contextlib.nullcontext()):
                    t_grid, cpu_grid = (time.perf_counter(),
                                        time.process_time())
                    with jax.profiler.TraceAnnotation("bench.grid"):
                        grids.append(run_sweep(sweep_cells, workers=1))
                    grid_s.append(time.perf_counter() - t_grid)
                    grid_cpu.append(time.process_time() - cpu_grid)
                seeds.append(trace_seed)
                fitted.append(sink)
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    trained = [by_family([trained_record(*got) for got in sink])
               for sink in fitted]
    return Window(grids, trained, seeds, elapsed, compiles.n, grid_s,
                  grid_cpu)


def failed_rows(rows: List[Dict]) -> int:
    """Rows quarantined or replayed on any backend but the lanes."""
    return sum(1 for r in rows
               if r.get("quarantined") or r.get("backend") != "pallas")


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             log=print) -> Dict:
    """Set-up, window and checks of one run; returns the result line."""
    from bench import compare, trace_reduce

    import jax
    from repro import compile_cache
    from repro.uvm.sweep import SweepCell, load_trace

    devs = require_chips(cell.chips) if require_chip else jax.devices()
    compile_cache.enable()
    compiles = CompileCounter()
    conf = cell.config
    t_dev = time.perf_counter()
    program_traces, one_pass = {}, []
    for ts in trace_order(cell, seed):
        program_traces[ts] = load_trace(conf["bench"], conf["scale"], ts,
                                        conf["window"])
        check_pins(program_traces[ts], conf)
        one_pass.append((ts, [SweepCell(**c) for c in grid(cell, ts)]))
    t_traces = time.perf_counter()
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    # warm-up: one whole grid compiles and runs every lane shape the
    # window will use (the traces of a cell share their shapes); a
    # predictor's shapes follow its trace's vocabulary, so a grid that
    # trains one warms up on every trace
    run_window(one_pass if trains(one_pass[0][1]) else one_pass[:1], 0.0,
               compiles)
    setup_s = time.perf_counter() - t_start
    log(f"bench: set-up {setup_s:.3f}s (start and device "
        f"{t_dev - t_start:.3f}s, traces {t_traces - t_dev:.3f}s, warm-up "
        f"grid {time.perf_counter() - t_traces:.3f}s), {compiles.n} "
        f"compilations, {compiles.cache_hits} from the persistent cache")
    win = run_window(one_pass, seconds, compiles, trace_dir=tmp)
    rss_mib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    mem = devs[0].memory_stats() or {}
    rows = [r for g in win.grids for r in g]
    log(f"bench: window {win.seconds:.3f}s, {len(win.grids)} grids, "
        f"{len(rows)} rows, {win.compiles} compilations; a grid by trace, "
        f"host seconds/process CPU seconds: " + ", ".join(
            f"{ts}:{sec:.3f}/{cpu:.3f}" for ts, sec, cpu in
            zip(win.trace_seeds, win.grid_seconds, win.grid_cpu_seconds)))

    result: Dict = {"correct": False,
                    "attempted": len(win.grids) * len(one_pass[0][1]),
                    "failed": failed_rows(rows), "metrics": {}}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    lane_accesses = sum(int(r["n_accesses"] or 0) for r in rows
                        if r.get("backend") == "pallas")
    if trace:
        red = trace_reduce.reduce_dir(tmp)
        shutil.rmtree(tmp, ignore_errors=True)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        ctx = trace_reduce.MetricContext(
            reduced=red, cell=cell, rows=rows, window=win,
            lane_accesses=lane_accesses, device_kind=devs[0].device_kind,
            program_traces=program_traces)
        for m in cell.per_layer:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = red.breakdown()
    else:
        e2e = {"accesses_per_s": (sum(int(r["n_accesses"] or 0)
                                      for r in rows) / win.seconds),
               "host_peak_rss_mib": rss_mib, "setup_s": setup_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    result["device"] = device

    # correctness: every row of the window against the plain reference
    checks = compare.check_window(
        conf, program_traces,
        list(zip(win.trace_seeds, win.grids, win.trained)), dict(one_pass))
    limits = cell.workload["limits"]
    result["correct"] = all(checks[k] <= limits[k] for k in limits)
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in limits}
    return result
