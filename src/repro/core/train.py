"""Training loop for the predictors (in-repo AdamW, jitted steps)."""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import model as model_lib
from repro.core.dataset import SequenceDataset, batches
from repro.core.metrics import topk_accuracy, weighted_f1
from repro.optimizer import AdamW, linear_warmup_cosine


@dataclasses.dataclass
class TrainResult:
    params: Dict
    cfg: model_lib.PredictorConfig
    metrics: Dict[str, float]
    steps: int
    train_seconds: float


def _loss_fn(cfg, params, x, y):
    logits = model_lib.apply(cfg, params, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
    return nll


def make_train_step(cfg: model_lib.PredictorConfig, *, steps: int,
                    lr: float = 3e-3):
    """The optimizer and the jitted train step of :func:`train_predictor`:
    ``step_fn(params, opt_state, x, y, step) -> (params, opt_state,
    loss)``, with a warmup-cosine learning rate over ``steps``."""
    obs.count("predictor.train_step_builds")
    opt = AdamW(weight_decay=1e-4, clip_norm=1.0)
    sched = linear_warmup_cosine(lr, warmup_steps=min(50, steps // 10 + 1),
                                 total_steps=steps)

    @jax.jit
    def step_fn(params, opt_state, x, y, step):
        loss, grads = jax.value_and_grad(
            lambda p: _loss_fn(cfg, p, x, y))(params)
        params, opt_state = opt.update(grads, params, opt_state, sched(step))
        return params, opt_state, loss

    return opt, step_fn


def train_predictor(cfg: model_lib.PredictorConfig, data: SequenceDataset,
                    *, steps: int = 400, batch_size: int = 128,
                    lr: float = 3e-3, seed: int = 0,
                    params=None, eval_topk: int = 10,
                    log_every: int = 0) -> TrainResult:
    key = jax.random.PRNGKey(seed)
    if params is None:
        params = model_lib.init_params(cfg, key)
    opt, step_fn = make_train_step(cfg, steps=steps, lr=lr)
    opt_state = opt.init(params)

    t0 = time.time()
    it = batches(data.x_train, data.y_train, batch_size, seed=seed,
                 epochs=max(1, steps * batch_size // max(len(data.x_train), 1) + 1))
    n_done = 0
    for x, y in it:
        params, opt_state, loss = step_fn(params, opt_state,
                                          jnp.asarray(x), jnp.asarray(y),
                                          jnp.asarray(n_done))
        n_done += 1
        if log_every and n_done % log_every == 0:
            print(f"  step {n_done}/{steps} loss={float(loss):.4f}")
        if n_done >= steps:
            break
    train_seconds = time.time() - t0

    metrics = evaluate(cfg, params, data, topk=eval_topk)
    return TrainResult(params=params, cfg=cfg, metrics=metrics,
                       steps=n_done, train_seconds=train_seconds)


def evaluate(cfg, params, data: SequenceDataset, topk: int = 10,
             split: str = "test", batch_size: int = 512) -> Dict[str, float]:
    x = getattr(data, f"x_{split}")
    y = getattr(data, f"y_{split}")
    logits = predict_logits(cfg, params, x, batch_size)
    return {
        "top1": topk_accuracy(logits, y, 1),
        f"top{topk}": topk_accuracy(logits, y, topk),
        "f1": weighted_f1(logits, y),
        "n": float(len(y)),
    }


_APPLY_CACHE: dict = {}


def _jitted_apply(cfg):
    fn = _APPLY_CACHE.get(cfg)
    if fn is None:
        fn = jax.jit(lambda p, xb: model_lib.apply(cfg, p, xb))
        _APPLY_CACHE[cfg] = fn
    return fn


def _jitted_cls_conf(cfg):
    """Fused top-1 class + softmax confidence: argmax/normalization run on
    device and only two scalars per window cross back to the host, instead
    of a full ``n_classes``-wide logits row."""
    fn = _APPLY_CACHE.get((cfg, "cls_conf"))
    if fn is None:
        def _cls_conf(p, xb):
            logits = model_lib.apply(cfg, p, xb)
            cls = jnp.argmax(logits, axis=-1)
            conf = jnp.max(jax.nn.softmax(logits, axis=-1), axis=-1)
            return cls, conf
        fn = jax.jit(_cls_conf)
        _APPLY_CACHE[(cfg, "cls_conf")] = fn
    return fn


def _pad_batches(x: np.ndarray, batch_size: int):
    """Yield (batch, pad) pairs of fixed shape (pad-and-mask): every batch
    has exactly ``batch_size`` rows, so jit traces one shape no matter how
    ragged the caller's windows are."""
    for i in range(0, len(x), batch_size):
        xb = x[i:i + batch_size]
        pad = 0
        if len(xb) < batch_size:
            pad = batch_size - len(xb)
            xb = np.concatenate([xb, np.zeros((pad,) + xb.shape[1:],
                                              xb.dtype)])
        yield xb, pad


def predict_logits(cfg, params, x: np.ndarray,
                   batch_size: int = 512) -> np.ndarray:
    apply_j = _jitted_apply(cfg)
    outs = []
    for xb, pad in _pad_batches(x, batch_size):
        o = np.asarray(apply_j(params, jnp.asarray(xb)))
        outs.append(o[:batch_size - pad] if pad else o)
    return np.concatenate(outs)


def predict_cls_conf(cfg, params, x: np.ndarray,
                     batch_size: int = 4096):
    """Top-1 class ids + their softmax probabilities for every row of ``x``,
    evaluated in large fixed-shape jitted batches.

    This is the serving path for ``PredictorService.predict_trace``: one
    compile per (cfg, batch, seq) shape, device-side argmax/softmax, and a
    2-column host transfer — several-fold faster than materializing logits
    per cluster slice.
    """
    if len(x) == 0:
        return (np.empty(0, dtype=np.int64), np.empty(0, dtype=np.float32))
    fn = _jitted_cls_conf(cfg)
    cls_out, conf_out = [], []
    for xb, pad in _pad_batches(x, batch_size):
        c, p = fn(params, jnp.asarray(xb))
        c, p = np.asarray(c), np.asarray(p)
        if pad:
            c, p = c[:-pad], p[:-pad]
        cls_out.append(c)
        conf_out.append(p)
    return (np.concatenate(cls_out).astype(np.int64),
            np.concatenate(conf_out))
