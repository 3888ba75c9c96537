"""The paper's learned prefetcher (arXiv:2203.12672 §4, §7.3), replayed with
the predictions that the program's predictor made on the chip.

The predictor sits at the UVM backend and serves one GMMU request at a
time: an access that finds it free takes a prediction and keeps it busy
for ``prediction_us`` (paper Fig 10), and one that finds it busy gets
none.  After each access the top-1 predicted page is migrated on its own
when it is a page, not the accessed one, and not resident; a far fault
also migrates the rest of its 64 KB block.  Both pay the inference time
before they reach the bus.

The predictions come from a predictor trained inside the grid, so the
replay takes them from the record of what the chip trained
(``cell["trained"]``, ``bench/reference/family.py``).  :func:`checks`
holds that record against the reference, on inputs rebuilt from the
reference trace (``predictor_inputs``):

* the training: the rows of the batches the program's first steps were
  fed are training windows of the trace with their labels, the steps
  the program ran are the cell's ``service_steps``, and the first
  :data:`predictor_training.STEPS` steps, followed by the reference from
  the parameters the program started from, give the program's losses,
  first gradient and change (``predictor_training``);
* the inference: a forward pass of the trained parameters over every
  window gives the program's top-1 confidences and, after the gate, the
  pages it served.

The forward of each model family is ``bench/reference/predictors/
<model_family>.py``, found by name: ``forward(params, config, windows)
-> logits`` in float32, written in ``jax.numpy`` so that the reference
can differentiate it, and run at ``highest`` matmul precision; and
``control_forward``, the same one step below, in bfloat16.
"""
import json
import os

import numpy as np

from bench.modules import load_module
from bench.reference import predictor_inputs, predictor_training
from bench.reference.family import Prefetcher, block_pages
from bench.reference.replay import CORE_MHZ

TRAINED = True
#: the page id and its prediction (two int32): the lane's ``preds`` input
INPUT_BYTES_PER_ACCESS = 8
#: where the forward of each model family lives, by its name
PREDICTOR_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "predictors")
#: rows of one forward call (the last block is padded to it)
BLOCK = 4096
#: the family's numbers, and how the rows of a window merge them
CHECKS = {"pred_conf_gap": "max", "pred_page_mismatches": "sum",
          "pred_near_ties": "sum", "train_loss_gap": "max",
          "train_grad_gap": "max", "train_update_gap": "max",
          "train_steps_missing": "sum", "train_label_mismatches": "sum"}


def state_bytes(working_set_pages: int) -> int:
    return 0


class Learned(Prefetcher):
    def __init__(self, pages, predictions, extra_latency_cycles) -> None:
        self.pages = [int(p) for p in pages]
        self.predictions = [int(p) for p in predictions]
        self.extra_latency_cycles = extra_latency_cycles
        self.next_free = 0.0

    def on_fault(self, index, page, resident):
        return block_pages(page, resident)

    def on_access(self, index, resident, clock):
        if clock < self.next_free:
            return []
        self.next_free = clock + self.extra_latency_cycles
        pred = self.predictions[index]
        if pred >= 0 and pred != self.pages[index] and pred not in resident:
            return [pred]
        return []


def make(trace, cell) -> Prefetcher:
    rec = cell.get("trained")
    if rec is None:
        raise ValueError("a learned row needs the record of what the "
                         "program trained for it")
    return Learned(trace.pages, rec["preds"],
                   float(cell["prediction_us"]) * CORE_MHZ)


def predictor(model_family: str):
    """The forward module of ``model_family``; a missing one is refused,
    naming its file."""
    return load_module(PREDICTOR_DIR, model_family,
                       "reference forward of the predictor family")


def resolve(cell) -> None:
    predictor(cell["model_family"])


#: jitted forwards and gradients, by function, configuration and
#: precision: the records of a window share their configuration
_jits: dict = {}


def _jitted(fn, config, precision, grad=False):
    """``fn(params, config, windows)`` jitted for one configuration, at
    matmul ``precision`` (None: the one ``fn`` states itself); with
    ``grad``, the mean cross-entropy of its logits at labels ``y`` and
    that loss's gradient, ``(params, x, y) -> (loss, grads)``."""
    import jax
    import jax.numpy as jnp

    key = (fn, json.dumps(config, sort_keys=True), precision, grad)
    if key in _jits:
        return _jits[key]

    def logits(params, x):
        if precision is None:
            return fn(params, config, x)
        with jax.default_matmul_precision(precision):
            return fn(params, config, x)

    def loss(params, x, y):
        logp = jax.nn.log_softmax(logits(params, x).astype(jnp.float32))
        return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()

    _jits[key] = jax.jit(jax.value_and_grad(loss) if grad else logits)
    return _jits[key]


def _probabilities(forward, rec, windows, precision) -> np.ndarray:
    """Softmax of ``forward``'s logits over ``windows``, in float64, in
    blocks of :data:`BLOCK` rows (of the least power of two that holds a
    trace with fewer windows)."""
    fn = _jitted(forward, rec["config"], precision)
    block = min(BLOCK, 1 << max(len(windows) - 1, 0).bit_length())
    out = []
    for i in range(0, len(windows), block):
        x = windows[i:i + block]
        n = len(x)
        if n < block:
            x = np.concatenate([x, np.zeros((block - n,) + x.shape[1:],
                                            x.dtype)])
        logits = np.asarray(fn(rec["params"], x), np.float64)[:n]
        z = np.exp(logits - logits.max(axis=1, keepdims=True))
        out.append(z / z.sum(axis=1, keepdims=True))
    return np.concatenate(out) if out else np.zeros((0, 1))


def _gated(inputs, probs) -> np.ndarray:
    """The page each window predicts after the confidence gate; -1 where
    its best class is unseen or under the gate."""
    best = probs.argmax(axis=1)
    conf = probs.max(axis=1)
    return np.where((best == predictor_inputs.UNK)
                    | (conf < predictor_inputs.MIN_PROB), -1,
                    inputs.decode(best))


def _served(inputs, probs, preds, conf) -> dict:
    """The inference numbers for served pages ``preds`` and top-1
    confidences ``conf`` (one per window) against the reference's
    probabilities ``probs`` (None: a vocabulary the reference does not
    rebuild, so nothing matches).

    A window whose two best classes, or whose best class and the gate,
    lie within twice the widest confidence gap of the record is a near
    tie: a forward that reads the confidences that far apart may decide
    it either way, so it is counted apart and never as a page mismatch.
    The band follows from the gap the run measures, which
    ``pred_conf_gap`` holds to its own limit."""
    preds = np.asarray(preds, np.int64)
    if probs is None:
        return {"pred_conf_gap": 1.0,
                "pred_page_mismatches": int(np.count_nonzero(preds >= 0)),
                "pred_near_ties": 0}
    ranked = np.sort(probs, axis=1)
    p1 = ranked[:, -1]
    p2 = ranked[:, -2] if probs.shape[1] > 1 else np.zeros(len(p1))
    conf = np.asarray(conf, np.float64)
    if len(conf) != len(p1):
        gap = 1.0
    else:
        gap = float(np.abs(conf - p1).max()) if len(p1) else 0.0
    band = 2 * gap
    near = (p1 - p2 < band) | (np.abs(p1 - predictor_inputs.MIN_PROB) < band)
    differ = preds[inputs.ends] != _gated(inputs, probs)
    # a prediction at an access that has no window is never the
    # predictor's
    outside = np.ones(len(preds), bool)
    outside[inputs.ends] = False
    mism = (int(np.count_nonzero(differ & ~near))
            + int(np.count_nonzero(outside & (preds >= 0))))
    return {"pred_conf_gap": gap, "pred_page_mismatches": mism,
            "pred_near_ties": int(np.count_nonzero(near))}


def _label_mismatches(inputs, batches) -> int:
    """Rows of the fed ``batches`` that are no training window of the
    trace with its label."""
    n = len(inputs.windows)
    rows = inputs.windows.reshape(n, -1)[inputs.train]
    labels = {}
    for row, lab in zip(rows, inputs.labels[inputs.train]):
        labels.setdefault(row.tobytes(), set()).add(int(lab))
    bad = 0
    for x, y in batches:
        x = np.ascontiguousarray(x, np.int32).reshape(len(x), -1)
        bad += sum(int(lab) not in labels.get(row.tobytes(), ())
                   for row, lab in zip(x, y))
    return bad


def _trained(inputs, cells, rec, forward, control=None) -> dict:
    """The training numbers of record ``rec``: the program's steps, or
    with ``control`` the reference's own steps through the control
    forward, against the reference's through ``forward``."""
    steps = [int(c["service_steps"]) for c in cells]
    tr = rec.get("training")
    if tr is None:
        return {"train_loss_gap": 1.0, "train_grad_gap": 1.0,
                "train_update_gap": 1.0, "train_steps_missing": max(steps),
                "train_label_mismatches": 0}
    follow = predictor_training.follow
    ref = follow(_jitted(forward, rec["config"], "highest", grad=True),
                 tr["init"], tr["batches"], steps[0])
    if control is None:
        got = (list(tr["losses"]),
               [m / (1 - predictor_training.BETA1)
                for m in predictor_training.leaves(tr["first_moment"])],
               predictor_training.leaves(tr["params"]))
        missing = max(abs(s - int(tr["steps_run"])) for s in steps)
    else:
        got = follow(_jitted(control, rec["config"], None, grad=True),
                     tr["init"], tr["batches"], steps[0])
        missing = 0
    out = predictor_training.readings(ref, got,
                                      predictor_training.leaves(tr["init"]))
    out["train_steps_missing"] = missing
    out["train_label_mismatches"] = _label_mismatches(inputs, tr["batches"])
    return out


def checks(trace, cells, produced) -> dict:
    """What the program's predictor produced for the rows of ``cells``
    (one record), against the reference: the training numbers
    (``predictor_training``; ``train_steps_missing``, the steps the
    program ran short of or beyond the cells' ``service_steps``;
    ``train_label_mismatches``, fed rows that are no training window with
    its label), and the inference numbers: ``pred_conf_gap``, the widest
    gap between the program's top-1 softmax confidence of a window and
    the reference's; ``pred_page_mismatches``, windows whose gated,
    decoded page differs from the one served, near ties left out, and
    served predictions where no window is; ``pred_near_ties``, the
    windows left out."""
    fwd = predictor(produced["model_family"]).forward
    inputs = predictor_inputs.build(trace.accesses,
                                    produced["config"]["features"])
    probs = None
    if inputs.n_classes == produced["config"]["n_classes"]:
        probs = _probabilities(fwd, produced, inputs.windows, "highest")
    out = _served(inputs, probs, produced["preds"], produced["conf"])
    out.update(_trained(inputs, cells, produced, fwd))
    return out


def control_checks(trace, cells, produced) -> dict:
    """The same numbers with the control forward, one step below, in the
    program's place: its gated pages served, its confidences read and its
    own steps followed instead of the program's."""
    mod = predictor(produced["model_family"])
    inputs = predictor_inputs.build(trace.accesses,
                                    produced["config"]["features"])
    probs = None
    preds = np.full(len(trace.accesses), -1, np.int64)
    conf = np.zeros(0)
    if inputs.n_classes == produced["config"]["n_classes"]:
        probs = _probabilities(mod.forward, produced, inputs.windows,
                               "highest")
        ctl = _probabilities(mod.control_forward, produced, inputs.windows,
                             None)
        preds[inputs.ends] = _gated(inputs, ctl)
        conf = ctl.max(axis=1)
    out = _served(inputs, probs, preds, conf)
    out.update(_trained(inputs, cells, produced, mod.forward,
                        control=mod.control_forward))
    return out
