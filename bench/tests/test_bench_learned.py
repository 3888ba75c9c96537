"""The learned prefetcher in the benchmark, at a size a test run holds.

A grid of ``learned`` cells trains its predictor in every grid of a
window, and the comparison holds its rows against the reference replay
given what was trained, and its predictions against a plain forward of
the trained parameters.  The forward here is a fixture module that wraps
the program's own ``apply`` (a test may import the program; the
reference may not): it stands in for the model family's plain forward
under ``bench/reference/predictors/``.
"""
import json
import os
import shutil
import textwrap

import numpy as np
import pytest

from bench import compare, harness
from bench.reference import family, predictor_inputs, tracegen

SCALE = 0.25
#: the program's trainer runs ``steps * 128 // n + 1`` epochs of
#: ``n // 128`` batches over ``n`` training windows: at this scale
#: (``n`` 862 and 863) it runs 18 of 20 steps asked for, and all 18
#: (:func:`test_a_faulty_training_is_not_correct`, ``short``)
STEPS = 18
FAMILY = "transformer"
PREDICTION_US = (0.0, 1.0, 3.0)
TRACES = (0, 1)
#: limits of a test run on the CPU, where the program's float32 forward
#: and steps are the reference's to rounding.  Readings on the two
#: traces of :data:`TRACES`, the program against the control (weights
#: and matmuls in bfloat16): ``pred_conf_gap`` 8.7e-8 against 1.2e-8 and
#: 2.5e-5 (a saturated predictor: the training numbers fail the
#: control), ``train_loss_gap`` 5.1e-6 against 1.7e-3 and 1.2e-2,
#: ``train_grad_gap`` 5.2e-8 against 3.2e-3 and 4.2e-3,
#: ``train_update_gap`` 7.7e-6 against 2.4e-3 and 5.7e-3
LIMITS = {"trace_records_differ": 0, "int_mismatches": 0,
          "float_rel_gap": 1e-6, "pred_conf_gap": 1e-6,
          "pred_page_mismatches": 0, "train_loss_gap": 1e-4,
          "train_grad_gap": 1e-5, "train_update_gap": 2e-4,
          "train_steps_missing": 0, "train_label_mismatches": 0}

#: the fixture forward: the program's ``apply``, and its control with
#: every weight and matmul in bfloat16; ``PERTURB`` moves the logits
#: (``swap``: the two best classes trade places, by their own margin;
#: ``noise``: each class's logit by at most a few 1e-4, which moves the
#: confidences and decides no page otherwise)
FORWARD = textwrap.dedent('''
    import jax
    import jax.numpy as jnp

    from repro.core import model
    from repro.core.families import PredictorConfig

    PERTURB = {perturb!r}


    def _cfg(config):
        return PredictorConfig(**dict(config,
                                      features=tuple(config["features"])))


    def forward(params, config, windows):
        logits = model.apply(_cfg(config), params, windows)
        if PERTURB == "swap":
            order = jnp.argsort(logits, axis=1)
            rows = jnp.arange(logits.shape[0])
            a, b = order[:, -1], order[:, -2]
            la, lb = logits[rows, a], logits[rows, b]
            logits = logits.at[rows, a].set(lb).at[rows, b].set(la)
        elif PERTURB == "noise":
            logits = logits + jnp.float32(1e-4) * jnp.arange(
                logits.shape[1], dtype=jnp.float32)
        return logits


    def control_forward(params, config, windows):
        low = jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.bfloat16).astype(jnp.float32),
            params)
        with jax.default_matmul_precision("bfloat16"):
            return model.apply(_cfg(config), low, windows)
''')


def _forward_dir(tmp_path, perturb=None) -> str:
    d = tmp_path / f"predictors_{perturb or 'plain'}"
    d.mkdir(exist_ok=True)
    (d / f"{FAMILY}.py").write_text(FORWARD.format(perturb=perturb))
    return str(d)


def _bm():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _traffic(root, limits=LIMITS, name="atax.learned-test",
             steps=STEPS) -> str:
    """A learned traffic file under ``root`` (a tree beside the
    repository's), with ATAX's configuration file."""
    os.makedirs(os.path.join(root, "bench", "workloads"), exist_ok=True)
    os.makedirs(os.path.join(root, "bench", "configs"), exist_ok=True)
    shutil.copy(os.path.join(harness.ROOT, "bench", "configs", "atax.json"),
                os.path.join(root, "bench", "configs", "atax.json"))
    with open(os.path.join(root, "bench", "workloads", f"{name}.json"),
              "w") as f:
        json.dump({"config": "atax", "chips": 1, "why": "test",
                   "trace_seeds": list(TRACES),
                   "fixed": {"service_steps": steps, "model_family": FAMILY},
                   "grid": {"prefetcher": ["learned"],
                            "prediction_us": list(PREDICTION_US),
                            "device_frac": [0.5], "eviction": ["lru"]},
                   "limits": limits}, f)
    return name


def _cell(root, steps=STEPS) -> harness.Cell:
    name = _traffic(root, steps=steps)
    cell = harness.cell_from_files(name, name, "atax", 1, _bm(), root)
    config = dict(cell.config, scale=SCALE)
    tr = tracegen.build_trace(config, TRACES[0])
    config["pins"] = {"n_accesses": len(tr.accesses),
                      "n_instructions": tr.n_instructions}
    cell.config, cell.backend = config, "pallas"
    return cell


def _one_pass(cell):
    from repro.uvm.sweep import SweepCell

    return [(ts, [SweepCell(**c) for c in harness.grid(cell, ts)])
            for ts in cell.workload["trace_seeds"]]


def _program_traces(cell):
    from repro.uvm.sweep import load_trace

    conf = cell.config
    return {ts: load_trace(conf["bench"], conf["scale"], ts, conf["window"])
            for ts in cell.workload["trace_seeds"]}


def _check(cell, win, one_pass, trained=None):
    grids = list(zip(win.trace_seeds, win.grids,
                     win.trained if trained is None else trained))
    return compare.check_window(cell.config, _program_traces(cell), grids,
                                dict(one_pass))


def _correct(checks) -> bool:
    return all(checks[k] <= v for k, v in LIMITS.items())


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """A window of two passes over two traces, with the program's
    ``predcache.misses`` counted grid by grid."""
    from repro import obs
    from repro.uvm import sweep

    tmp = tmp_path_factory.mktemp("learned")
    misses = []
    orig = sweep.run_sweep

    def counted(cells, **kw):
        obs.take()
        rows = orig(cells, **kw)
        misses.append(obs.take().counters.get("predcache.misses", 0))
        return rows

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(family.load("learned"), "PREDICTOR_DIR",
                   _forward_dir(tmp))
        cell = _cell(str(tmp / "root"))
        one_pass = _one_pass(cell)
        mp.setattr(sweep, "run_sweep", counted)
        with obs.record():
            win = harness.run_window(one_pass + one_pass, 0.0,
                                     harness.CompileCounter())
    return {"cell": cell, "one_pass": one_pass, "win": win,
            "misses": misses}


@pytest.fixture
def forward(monkeypatch, tmp_path):
    """Points the learned family at a fixture forward."""
    mod = family.load("learned")

    def use(perturb=None):
        monkeypatch.setattr(mod, "PREDICTOR_DIR",
                            _forward_dir(tmp_path, perturb))
    use()
    return use


def test_every_grid_trains_its_predictor(run):
    win = run["win"]
    assert win.trace_seeds == list(TRACES) * 2
    # one (trace, model) pair a grid, and no grid reuses another's
    # predictions: each trains.  The program trains the pair once per
    # learned cell, since the cells prepare at the same time and miss
    # the memo together; the records of one grid are then the same
    assert len(run["misses"]) == len(win.grids)
    assert all(m >= 1 for m in run["misses"]), run["misses"]
    assert all(set(t) == {FAMILY} and t[FAMILY] is not None
               for t in win.trained)
    keys = [t[FAMILY]["trace"] for t in win.trained]
    assert keys[:2] == keys[2:] and keys[0] != keys[1]
    for t in win.trained:
        rec = t[FAMILY]
        assert rec["config"]["n_classes"] == rec["params"]["head"].shape[1]
        leaves = [rec["params"]["head"], rec["params"]["layers"][0]["wq"]]
        assert all(a.dtype == np.float32 for a in leaves)
    # the program's service and trainer are as they were once the window
    # has closed
    from repro.core import service, train
    assert service.predict_cls_conf is train.predict_cls_conf
    assert (service.PredictorService.predict_trace.__qualname__
            == "PredictorService.predict_trace")
    assert train.make_train_step.__qualname__ == "make_train_step"


def test_the_record_holds_what_the_program_computed(run):
    """The recording reaches the program's inference and train step: a
    confidence for every window and the first steps' inputs and outputs.
    It wraps ``PredictorService.predict_trace``, ``service.
    predict_cls_conf`` and ``train.make_train_step``, so a program that
    stops calling through them leaves the record empty, and this fails
    before the comparison reads an empty record as a gap of 1."""
    from bench.reference import predictor_training

    rec = run["win"].trained[0][FAMILY]
    tr = tracegen.build_trace(run["cell"].config, run["win"].trace_seeds[0])
    inputs = predictor_inputs.build(tr.accesses, rec["config"]["features"])
    assert len(rec["conf"]) == len(inputs.windows) > 0
    training = rec["training"]
    assert training["steps_run"] == STEPS
    assert (len(training["batches"]) == len(training["losses"])
            == predictor_training.STEPS)
    assert all(len(x) == len(y) == 128 for x, y in training["batches"])
    leaves = predictor_training.leaves
    assert len(leaves(training["init"])) == len(leaves(rec["params"]))
    assert any(np.any(a != b) for a, b in zip(leaves(training["params"]),
                                              leaves(training["init"])))


def test_family_numbers_count_once_per_record(run, forward):
    """A family reports once for what it produced in a grid, not once for
    each cell the record served: a grid of three learned cells reads as
    one of them does."""
    win, one_pass = run["win"], run["one_pass"]
    one = [(ts, cells[:1]) for ts, cells in one_pass]
    rows = [[r for r in g if r["prediction_us"] == PREDICTION_US[0]]
            for g in win.grids]
    all_cells = _check(run["cell"], win, one_pass)
    first = compare.check_window(
        run["cell"].config, _program_traces(run["cell"]),
        list(zip(win.trace_seeds, rows, win.trained)), dict(one))
    fam = family.load("learned").CHECKS
    assert {k: all_cells[k] for k in fam} == {k: first[k] for k in fam}


def test_a_grid_without_a_trained_family_is_left_alone():
    from repro.uvm import predcache
    from repro.uvm.sweep import SweepCell

    sentinel = np.zeros(1)
    predcache._MEMO["bench-test-sentinel"] = sentinel
    try:
        cells = [SweepCell(bench="ATAX", prefetcher="none", scale=SCALE,
                           device_frac=0.5, backend="numpy")]
        win = harness.run_window([(0, cells)], 0.0,
                                 harness.CompileCounter())
        assert predcache._MEMO.get("bench-test-sentinel") is sentinel
        assert win.trained == [{}]
    finally:
        predcache._MEMO.pop("bench-test-sentinel", None)


@pytest.mark.parametrize("us", PREDICTION_US)
def test_learned_rows_are_correct(run, forward, us):
    win, one_pass = run["win"], run["one_pass"]
    sub = [(ts, [c for c in cells if c.prediction_us == us])
           for ts, cells in one_pass]
    rows = [[r for r in g if r["prediction_us"] == us] for g in win.grids]
    grids = list(zip(win.trace_seeds, rows, win.trained))
    checks = compare.check_window(run["cell"].config,
                                  _program_traces(run["cell"]), grids,
                                  dict(sub))
    assert _correct(checks), checks
    assert checks["int_mismatches"] == 0
    assert all(r["backend"] == "pallas" for g in rows for r in g)


def test_a_learned_cell_runs_correct_through_the_harness(tmp_path, forward):
    """The benchmark's own run of a learned cell: warm-up on every trace,
    the window, and the comparison with the family's numbers beside
    their limits in the result line."""
    import time

    res = harness.run_cell(_cell(str(tmp_path)), 2 ** 31 + 3, 0.0, False,
                           t_start=time.perf_counter(), require_chip=False,
                           log=lambda _m: None)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert set(res["checks"]) == set(LIMITS)
    assert list(res)[-1] == "checks"


def test_the_inference_server_is_exercised(run):
    """A slower predictor serves fewer accesses: the rows of the three
    ``prediction_us`` differ."""
    issued = {r["prediction_us"]: r["prefetch_issued"]
              for r in run["win"].grids[0]}
    assert issued[0.0] > issued[1.0] > issued[3.0]


def test_a_missing_record_is_not_correct(run, forward):
    win = run["win"]
    trained = [dict(t) for t in win.trained]
    trained[1] = {}
    checks = _check(run["cell"], win, run["one_pass"], trained)
    assert not _correct(checks)
    assert checks["int_mismatches"] >= len(PREDICTION_US)


@pytest.mark.parametrize("perturb", ["swap", "noise"])
def test_a_perturbed_forward_is_not_correct(run, forward, perturb):
    """Logits moved beyond the near-tie band change the pages; moved far
    inside it, they change no page and show in the confidences."""
    forward(perturb)
    checks = _check(run["cell"], run["win"], run["one_pass"])
    assert not _correct(checks), checks
    if perturb == "swap":
        assert checks["pred_page_mismatches"] > 0
    else:
        assert checks["pred_page_mismatches"] == 0
        assert checks["pred_conf_gap"] > LIMITS["pred_conf_gap"]


def test_an_altered_prediction_is_not_correct(tmp_path, forward,
                                              monkeypatch):
    """One prediction off where the predictor produces it: the lanes and
    the record both get it, and the reference forward does not."""
    from repro.core.service import PredictorService

    predict = PredictorService.predict_trace

    def altered(self, *args, **kwargs):
        preds = predict(self, *args, **kwargs).copy()
        i = int(np.flatnonzero(preds >= 0)[0])
        preds[i] += 1
        return preds

    monkeypatch.setattr(PredictorService, "predict_trace", altered)
    cell = _cell(str(tmp_path / "root"))
    one_pass = _one_pass(cell)[:1]
    win = harness.run_window(one_pass, 0.0, harness.CompileCounter())
    checks = _check(cell, win, one_pass)
    assert not _correct(checks), checks
    assert checks["pred_page_mismatches"] >= 1


def _train_faults():
    """Faults planted in the program's training, by name: the train step
    returns its state unchanged; the loss is the mean over half of the
    batch; one label of the first batch is altered where the batches are
    drawn; the training asks for more steps than the trainer runs at this
    size (the program's own shortfall, see :data:`STEPS`)."""
    from repro.core import train

    make_step, loss_fn, draw = (train.make_train_step, train._loss_fn,
                                train.batches)

    def unchanged(*args, **kwargs):
        opt, step_fn = make_step(*args, **kwargs)

        def step(params, opt_state, x, y, i):
            return params, opt_state, step_fn(params, opt_state, x, y, i)[2]
        return opt, step

    def half(cfg, params, x, y):
        n = x.shape[0] // 2
        return loss_fn(cfg, params, x[:n], y[:n])

    def relabelled(*args, **kwargs):
        for i, (x, y) in enumerate(draw(*args, **kwargs)):
            if i == 0:
                y = y.copy()
                y[0] = 1 - y[0] if y[0] <= 1 else y[0] - 1
            yield x, y

    return {"unchanged": ("make_train_step", unchanged,
                          ("train_update_gap", "train_grad_gap")),
            "half_batch": ("_loss_fn", half, ("train_loss_gap",)),
            "relabelled": ("batches", relabelled,
                           ("train_label_mismatches",)),
            "short": (None, None, ("train_steps_missing",))}


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "relabelled",
                                   "short"])
def test_a_faulty_training_is_not_correct(tmp_path, forward, monkeypatch,
                                          fault):
    """A grid of one learned cell on one trace, trained with a fault in
    the program: the training numbers fail their limits."""
    from repro.core import train

    name, planted, fails = _train_faults()[fault]
    if planted is not None:
        monkeypatch.setattr(train, name, planted)
    cell = _cell(str(tmp_path / "root"),
                 steps=STEPS + 2 if fault == "short" else STEPS)
    one_pass = [(ts, [c for c in cells if c.prediction_us == 1.0])
                for ts, cells in _one_pass(cell)[:1]]
    win = harness.run_window(one_pass, 0.0, harness.CompileCounter())
    checks = _check(cell, win, one_pass)
    assert not _correct(checks), checks
    assert all(checks[k] > LIMITS[k] for k in fails), checks


def test_control_reports_the_family_numbers(run, forward):
    ts, cells = run["one_pass"][0]
    checks = compare.control_checks(run["cell"].config, ts, cells,
                                    run["win"].trained[0])
    assert set(checks) >= set(LIMITS) | {"pred_near_ties"}
    assert all(checks[k] > LIMITS[k]
               for k in ("train_loss_gap", "train_grad_gap",
                         "train_update_gap")), checks
    assert not _correct(checks)


def test_a_family_check_can_be_a_limit(tmp_path, forward):
    cell = _cell(str(tmp_path))
    assert set(cell.workload["limits"]) == set(LIMITS)


@pytest.mark.parametrize("limit", ["no_such_check", "pred_conf_gaps"])
def test_a_limit_naming_an_unknown_check_is_refused_at_load(tmp_path,
                                                             forward, limit):
    name = _traffic(str(tmp_path), dict(LIMITS, **{limit: 0}))
    with pytest.raises(harness.Refused,
                       match=f"bench/workloads/{name}.json.*{limit}"):
        harness.cell_from_files(name, name, "atax", 1, _bm(), str(tmp_path))


def test_a_missing_forward_is_refused_at_load(tmp_path, monkeypatch):
    mod = family.load("learned")
    monkeypatch.setattr(mod, "PREDICTOR_DIR",
                        os.path.join(harness.ROOT, "bench", "reference",
                                     "predictors"))
    name = _traffic(str(tmp_path))
    with pytest.raises(harness.Refused,
                       match=f"bench/reference/predictors/{FAMILY}.py"):
        harness.cell_from_files(name, name, "atax", 1, _bm(), str(tmp_path))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_inputs_equal_the_programs(seed):
    """The windows, their positions and the delta vocabulary that the
    reference rebuilds from its trace's records are the program's
    predictor inputs, at the timed size."""
    from repro.core.dataset import SEQ_LEN, build_dataset
    from repro.core.families import EMB_DIMS
    from repro.core.features import cluster_trace
    from repro.core.service import PredictorService
    from repro.core.vocab import DeltaVocab, encode_features
    from repro.uvm.sweep import load_trace

    conf = harness.load_cell("atax.replay").config
    svc = PredictorService()
    trace = load_trace(conf["bench"], conf["scale"], seed, conf["window"])
    ct = cluster_trace(trace, svc.cluster_key)
    vocab = DeltaVocab.build(ct, distance=svc.distance)
    features = list(EMB_DIMS)
    wins, ends = [], []
    for c, gidx in zip(ct.clusters, ct.global_index):
        if len(gidx) < SEQ_LEN:
            continue
        enc = encode_features(c, features)
        starts = np.arange(len(gidx) - SEQ_LEN + 1)
        wins.append(enc[starts[:, None] + np.arange(SEQ_LEN)])
        ends.append(gidx[starts + SEQ_LEN - 1])
    got = predictor_inputs.build(tracegen.build_trace(conf, seed).accesses,
                                 features)
    assert (predictor_inputs.SEQ_LEN, predictor_inputs.DISTANCE,
            predictor_inputs.MIN_PROB) == (svc.seq_len, svc.distance,
                                           svc.min_prob)
    np.testing.assert_array_equal(got.deltas, vocab.deltas)
    np.testing.assert_array_equal(got.ends, np.concatenate(ends))
    np.testing.assert_array_equal(got.windows, np.concatenate(wins))
    np.testing.assert_array_equal(
        got.end_pages, np.asarray(trace.accesses["page"])[got.ends])
    # the training split and its labels, as the program's dataset holds
    # them before it draws its batches (it keeps them all at this size)
    data = build_dataset(ct, vocab, features=features, seq_len=svc.seq_len,
                         distance=svc.distance, max_train=16000,
                         seed=svc.seed)
    np.testing.assert_array_equal(got.windows[got.train], data.x_train)
    np.testing.assert_array_equal(got.labels[got.train], data.y_train)
