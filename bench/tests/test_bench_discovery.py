"""Discovery of configurations, traffic and metric readers by name, and
the refusals: a trace that moved from its pins, a host without a TPU, a
directory without the program."""
import json
import os
import re
import shutil
import subprocess
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import harness

ROOT = harness.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BM = json.load(_f)


NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _check_reduced(conf, entry):
    """``reduced`` lists the keys cut from the source, the same in the
    configuration file and in ``BENCHMARK.json``; the file holds each
    cut key with its value as run."""
    assert isinstance(conf["reduced"], list)
    assert conf["reduced"] == entry["reduced"]
    for key in conf["reduced"]:
        assert isinstance(key, str) and NAME.match(key), key
        assert key in conf, key


@pytest.mark.parametrize("name", [w["name"] for w in BM["workloads"]])
def test_every_seed_replays_the_same_traces(name):
    """The seed draws the order of a pass, never its traces."""
    cell = harness.load_cell(name)
    pool = cell.workload["trace_seeds"]
    orders = [harness.trace_order(cell, s) for s in (2 ** 31 + 11, 3, 0)]
    assert all(sorted(o) == sorted(pool) for o in orders)
    assert harness.trace_order(cell, 2 ** 31 + 11) == orders[0]


@pytest.mark.parametrize("name", [w["name"] for w in BM["workloads"]])
def test_every_cell_resolves_by_name(name):
    cell = harness.load_cell(name)
    entry = next(w for w in BM["workloads"] if w["name"] == name)
    assert cell.config["name"] == entry["config"] == cell.workload["config"]
    assert cell.chips == entry["chips"] == cell.workload["chips"]
    _check_reduced(cell.config,
                   next(c for c in BM["configs"] if c["name"] == entry["config"]))
    assert set(cell.workload["limits"]) >= {
        "trace_records_differ", "int_mismatches", "float_rel_gap"}
    sweep = harness.grid(cell, 2 ** 31 + 5)
    n = 1
    for axis in cell.workload["grid"].values():
        n *= len(axis)
    assert len(sweep) == n
    assert all(c["seed"] == 2 ** 31 + 5 and c["bench"] ==
               cell.config["bench"] for c in sweep)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in BM["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    read = harness.load_reader(metric)
    assert callable(read)


def test_config_files_are_the_ones_benchmark_json_names():
    files = [c["file"] for c in BM["configs"]]
    assert len(set(files)) == len(files)
    for c in BM["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"]
        _check_reduced(conf, c)
        assert set(conf["pins"]) == {"n_accesses", "n_instructions"}


def test_unknown_cell_and_reader_are_refused():
    with pytest.raises(harness.Refused):
        harness.load_cell("no.such-cell")
    with pytest.raises(harness.Refused):
        harness.load_reader("no_such_metric")


def test_a_trace_that_moved_from_its_pins_is_refused():
    conf = harness.load_cell("atax.replay").config
    ok = NS(accesses=np.zeros(conf["pins"]["n_accesses"]),
            n_instructions=conf["pins"]["n_instructions"])
    harness.check_pins(ok, conf)
    moved = NS(accesses=np.zeros(conf["pins"]["n_accesses"] - 1),
               n_instructions=conf["pins"]["n_instructions"])
    with pytest.raises(harness.Refused, match="moved from its pins"):
        harness.check_pins(moved, conf)
    more = NS(accesses=ok.accesses,
              n_instructions=conf["pins"]["n_instructions"] + 1)
    with pytest.raises(harness.Refused, match="n_instructions"):
        harness.check_pins(more, conf)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"),
         "--workload", "atax.replay", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
