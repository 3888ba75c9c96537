"""The plain reference, found by name: every configuration's trace
generator and every grid prefetcher's family resolve, a missing one is
refused naming its file, the traces and rows of ATAX are those the
reference gave before it was split into modules, and a configuration
and a family each enter as a new file."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import compare, harness
from bench.reference import family, replay, tracegen

ROOT = harness.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BM = json.load(_f)
CELLS = {w["name"]: harness.load_cell(w["name"]) for w in BM["workloads"]}
CONFIGS = {c.config["name"]: c.config for c in CELLS.values()}
#: per configuration, the scale a test run holds (its first cell's)
SMALL_SCALE = {}
for _c in CELLS.values():
    SMALL_SCALE.setdefault(_c.config["name"], _c.workload["small"]["scale"])
PREFETCHERS = sorted({r["prefetcher"] for c in CELLS.values()
                      for r in harness.grid(c, 0)})

ATAX = {"name": "atax", "bench": "ATAX", "scale": 1.0, "window": 0.6}
#: sha256 of the records and instruction count of ATAX's reference trace
#: at the timed size, trace seeds 0-4, as the reference built them before
#: its generators moved into modules of their own
ATAX_TRACES = {
    0: "d47c8fa394a50f509f7d1beab7f8304b616d391456c4c1f54a3413a0419a4b89",
    1: "78340fa44462a892e1cd8e8b719f727fd374a4b1b0e3f16f4c056514b87d1631",
    2: "fbb1f315d1d10ccf2b15beab02f24c83f0e7cf52116d1a2ce2af4e269fb4ed45",
    3: "366bb46ef812d6159143bcbf8477a123a4e131e169fea2a8223e3d3efb2e3b7b",
    4: "244424621dafdfe65ef0c304e2ad0133d3746f95f3a1e683022d603b6195f687",
}
#: sha256 of the reference rows of none/block/tree/oracle x lru/random/
#: hotcold at device_frac 0.5 on ATAX at scale 0.25, trace seed 0, in
#: float64 (the reference) and float32 (the control), as the reference
#: gave them before its prefetchers moved into family modules
ATAX_SMALL_ROWS = {
    True: "967a85ab7e6158cf454e14c6989d52908742848b3026c6b71a2df12c94fe8bb0",
    False: "0b6e814073c3785087214813b6ef1dc9f9983d35b6e442615ad706ad19ba7c3a",
}
FAMILIES = ("none", "block", "tree", "oracle")
POLICIES = ("lru", "random", "hotcold")


def _trace_digest(tr) -> str:
    h = hashlib.sha256(tr.accesses.tobytes())
    h.update(str(int(tr.n_instructions)).encode())
    return h.hexdigest()


def _small_atax():
    return tracegen.build_trace(dict(ATAX, scale=0.25), 0)


def _cell(prefetcher, eviction, device_frac=0.5):
    return {"prefetcher": prefetcher, "eviction": eviction,
            "device_frac": device_frac}


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_config_has_a_trace_generator(name):
    assert callable(tracegen.generator(name).streams)


@pytest.mark.parametrize("name", PREFETCHERS)
def test_every_grid_prefetcher_has_a_family(name):
    mod = family.load(name)
    assert callable(mod.make)
    assert mod.INPUT_BYTES_PER_ACCESS > 0
    assert mod.state_bytes(0) == 0 <= mod.state_bytes(1000)
    pf = mod.make(_small_atax(), _cell(name, "lru"))
    assert pf.extra_latency_cycles == 0.0


def test_a_missing_reference_module_is_refused_naming_its_file():
    with pytest.raises(harness.Refused,
                       match="bench/reference/traces/no_such_config.py"):
        tracegen.generator("no_such_config")
    with pytest.raises(harness.Refused,
                       match="bench/reference/prefetchers/no_such_family.py"):
        family.load("no_such_family")
    with pytest.raises(harness.Refused, match="no_such_family.py"):
        replay.replay(_small_atax(), _cell("no_such_family", "lru"))


@pytest.mark.parametrize("missing", ["traces/atax.py", "prefetchers/tree.py"])
def test_run_refuses_a_missing_reference_module(tmp_path, missing):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    os.remove(tmp_path / "bench" / "reference" / missing)
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"),
         "--workload", "atax.replay", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "refused:" in proc.stderr
    assert f"bench/reference/{missing}" in proc.stderr


@pytest.mark.parametrize("seed", sorted(ATAX_TRACES))
def test_atax_reference_traces_are_unchanged(seed):
    assert _trace_digest(tracegen.build_trace(ATAX, seed)) \
        == ATAX_TRACES[seed]


@pytest.mark.parametrize("precise", [True, False],
                         ids=["reference", "control"])
def test_atax_reference_rows_are_unchanged(precise):
    tr = _small_atax()
    rows = {f"{pf}/{ev}": replay.replay(tr, _cell(pf, ev), precise=precise)
            for pf in FAMILIES for ev in POLICIES}
    blob = json.dumps(rows, sort_keys=True).encode()
    assert hashlib.sha256(blob).hexdigest() == ATAX_SMALL_ROWS[precise]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_configuration_enters_as_a_new_file(tmp_path, seed):
    """A configuration's stream generator is one new module, found by the
    configuration's name: a copy of ATAX's gives ATAX's trace."""
    shutil.copy(os.path.join(tracegen.TRACE_DIR, "atax.py"),
                tmp_path / "atax_copy.py")
    conf = dict(ATAX, name="atax_copy", scale=0.25)
    got = tracegen.build_trace(conf, seed, generators=str(tmp_path))
    want = tracegen.build_trace(dict(ATAX, scale=0.25), seed)
    assert _trace_digest(got) == _trace_digest(want)


def _family_copy(tmp_path, name, latency=None):
    src = open(os.path.join(family.PREFETCHER_DIR, "block.py")).read()
    if latency is not None:
        src = src.replace("class Block(Prefetcher):\n",
                          "class Block(Prefetcher):\n"
                          f"    extra_latency_cycles = {latency!r}\n\n")
    (tmp_path / f"{name}.py").write_text(src)
    return str(tmp_path)


@pytest.mark.parametrize("eviction", POLICIES)
def test_a_family_enters_as_a_new_file(tmp_path, eviction):
    tr = _small_atax()
    want = replay.replay(tr, _cell("block", eviction))
    got = replay.replay(tr, _cell("block_copy", eviction),
                        families=_family_copy(tmp_path, "block_copy"))
    assert got.pop("prefetcher") == "block_copy"
    want.pop("prefetcher")
    assert got == want


def test_extra_latency_delays_a_familys_prefetches(tmp_path):
    """A family's ``extra_latency_cycles`` is added to a prefetch's ready
    time, which then waits for the bus: below the far-fault path (1 us
    here) it changes nothing, since the fault's own transfer holds the
    bus longer; at three fault rounds the blocks arrive late."""
    tr = _small_atax()
    want = replay.replay(tr, _cell("block", "lru"))
    cycles_per_us = replay.CORE_MHZ
    near = replay.replay(tr, _cell("block_1us", "lru"), families=_family_copy(
        tmp_path, "block_1us", cycles_per_us))
    assert {k: v for k, v in near.items() if k != "prefetcher"} \
        == {k: v for k, v in want.items() if k != "prefetcher"}
    late = replay.replay(tr, _cell("block_late", "lru"), families=_family_copy(
        tmp_path, "block_late", 3 * replay.FAR_FAULT_US * cycles_per_us))
    assert late["late"] > want["late"]
    assert late["hits"] + late["late"] == want["hits"] + want["late"]
    for k in ("faults", "prefetch_issued", "pages_migrated",
              "pages_evicted"):
        assert late[k] == want[k]
    assert late["cycles"] != want["cycles"]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_trace_equals_the_programs(name):
    from repro.uvm.sweep import load_trace

    conf = dict(CONFIGS[name], scale=SMALL_SCALE[name])
    for seed in range(5):
        got = load_trace(conf["bench"], conf["scale"], seed, conf["window"])
        tr = tracegen.build_trace(conf, seed)
        assert compare.trace_checks(got, tr) == {"trace_records_differ": 0}
        assert got.n_instructions == tr.n_instructions


def _imports(path):
    import ast

    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield node.module or ""
            yield from (f"{node.module}.{a.name}" for a in node.names)


REFERENCE_FILES = sorted(
    os.path.relpath(os.path.join(d, f), ROOT)
    for d, _, fs in os.walk(os.path.join(ROOT, "bench", "reference"))
    for f in fs if f.endswith(".py")) + ["bench/modules.py"]


@pytest.mark.parametrize("path", REFERENCE_FILES)
def test_the_reference_imports_neither_the_program_nor_the_harness(path):
    """The reference stands below the harness that drives the timed run,
    and apart from the program it checks."""
    for name in _imports(os.path.join(ROOT, path)):
        assert not name.startswith(("repro", "bench.harness", "bench.compare",
                                    "bench.costs", "bench.run")), name
