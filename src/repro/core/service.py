"""Predictor service: the bridge between the trained model and the UVM
runtime (paper §7.1).

The paper pretrains one model on a 5-benchmark corpus (different input data),
then fine-tunes per benchmark every 50 M instructions and serves predictions
from the UVM backend with ~1 us inference latency.  Here:

* ``fit`` trains (optionally starting from corpus-pretrained params),
* ``predict_trace`` produces the per-access top-1 predicted page array the
  ``LearnedPrefetcher`` consumes: for every access i, the page the model
  expects ``distance`` requests later within i's cluster stream,
* inference latency is modeled in the simulator (Fig 10), not here.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from repro import obs
from repro.core import families
from repro.core import model as model_lib
from repro.core.dataset import SEQ_LEN, build_dataset
from repro.core.features import ClusteredTrace, cluster_trace, delta_convergence
from repro.core.train import TrainResult, predict_cls_conf, train_predictor
from repro.core.vocab import DeltaVocab, encode_features
from repro.traces.trace import Trace


@dataclasses.dataclass
class PredictorService:
    """Owns a (revised, by default) predictor for one benchmark."""

    # The paper's revised predictor clusters by SM+warp over 50M-instruction
    # windows; our traces are 10-100x shorter, so per-(SM,warp-slot) streams
    # are too short to window — the service defaults to SM clustering and
    # the SM+warp ablation lives in the Table 2 benchmark.
    cluster_key: str = "sm"
    # Prediction distance: the paper uses 30 for timeliness in its GMMU-rate
    # regime.  Our SM-cluster predictions interleave across 28 SMs, so a
    # distance-8 prediction already buys ~8*28 global requests of lead; 8
    # keeps labels within a CTA scheduling burst (far less label entropy).
    distance: int = 8
    min_prob: float = 0.35
    seq_len: int = SEQ_LEN
    steps: int = 300
    batch_size: int = 128
    quantize: bool = True
    bypass_threshold: float = 0.7
    seed: int = 0
    # which predictor family to assemble in fit() when no explicit cfg is
    # passed — "simplified" (§6 revised), "transformer" (the reference
    # encoder), or "transformer-local"; see repro.core.families
    model_family: str = "simplified"

    trace: Optional[Trace] = None
    ct: Optional[ClusteredTrace] = None
    vocab: Optional[DeltaVocab] = None
    result: Optional[TrainResult] = None
    convergence: float = 0.0

    @property
    def model_config(self) -> str:
        """Architecture digest of this service's family block, for cache
        keying (repro.uvm.predcache).  Trace-determined parts of the
        resolved config — n_classes and the convergence-driven bypass
        flip — are pinned to sentinels: the trace content is already part
        of every predcache key, so the digest only needs to capture the
        architecture the family + service knobs select."""
        cfg = families.family_config(self.model_family, n_classes=0,
                                     convergence=0.0,
                                     bypass_threshold=self.bypass_threshold,
                                     quantize=self.quantize)
        return families.config_digest(cfg)

    def fit(self, trace: Trace, init_params=None,
            cfg: model_lib.PredictorConfig | None = None,
            max_train: int = 16000) -> TrainResult:
        with obs.span("predictor.fit", steps=self.steps):
            self.trace = trace
            self.ct = cluster_trace(trace, self.cluster_key)
            self.vocab = DeltaVocab.build(self.ct, distance=self.distance)
            self.convergence = delta_convergence(self.ct)
            if cfg is None:
                cfg = model_lib.family_config(
                    self.model_family, self.vocab.n_classes, self.convergence,
                    self.bypass_threshold, quantize=self.quantize)
            data = build_dataset(self.ct, self.vocab,
                                 features=list(cfg.features),
                                 seq_len=self.seq_len, distance=self.distance,
                                 max_train=max_train, seed=self.seed)
            self.result = train_predictor(cfg, data, steps=self.steps,
                                          batch_size=self.batch_size,
                                          seed=self.seed, params=init_params)
            return self.result

    def predict_trace(self, trace: Trace | None = None,
                      batch_size: int = 4096) -> np.ndarray:
        """Per-access predicted pages, aligned with GMMU trace order.
        Entry i is the top-1 page expected ``distance`` accesses after i in
        i's cluster, or -1 where no prediction is available (window warmup or
        UNK class).

        Windows from *all* clusters are concatenated into one stream and
        pushed through ``predict_cls_conf`` in large fixed-shape jitted
        batches (pad-and-mask): small clusters no longer each pay a mostly-
        padded device batch, jit compiles one shape for the whole trace, and
        only the (class, confidence) pair per window crosses back to the
        host instead of full logits rows."""
        with obs.span("predictor.predict"):
            assert self.result is not None and self.vocab is not None
            if trace is None:
                ct = self.ct
            else:
                ct = cluster_trace(trace, self.cluster_key)
            cfg, params = self.result.cfg, self.result.params
            out = np.full(max(g.max() for g in ct.global_index) + 1, -1,
                          dtype=np.int64)
            window = np.arange(self.seq_len)[None, :]
            # windows accumulate across clusters but are inferred in shared
            # flushes of at most flush_windows rows, so peak memory is bounded
            # by the flush size, not the trace length
            flush_windows = max(batch_size, 65536)
            pend_x: list = []
            pend_spans: list = []
            pend_n = 0

            def _flush() -> None:
                nonlocal pend_x, pend_spans, pend_n
                if not pend_x:
                    return
                x = pend_x[0] if len(pend_x) == 1 else np.concatenate(pend_x)
                cls, conf = predict_cls_conf(cfg, params, x, batch_size)
                off = 0
                for pages, gidx, ends in pend_spans:
                    m = len(ends)
                    c, p = cls[off:off + m], conf[off:off + m]
                    off += m
                    deltas = self.vocab.decode(c)
                    # confidence gate: don't prefetch on low-probability
                    # predictions (useless prefetches cost bus bandwidth,
                    # §7.6)
                    pred_pages = np.where((c == 0) | (p < self.min_prob),
                                          -1, pages[ends] + deltas)
                    out[gidx[ends]] = pred_pages
                pend_x, pend_spans, pend_n = [], [], 0

            for cluster, pages, gidx in zip(ct.clusters, ct.pages,
                                            ct.global_index):
                n = len(pages)
                if n < self.seq_len:
                    continue
                enc = encode_features(cluster, list(cfg.features))
                all_starts = np.arange(0, n - self.seq_len + 1)
                for s0 in range(0, len(all_starts), flush_windows):
                    starts = all_starts[s0:s0 + flush_windows]
                    pend_x.append(enc[starts[:, None] + window])
                    pend_spans.append((pages, gidx, starts + self.seq_len - 1))
                    pend_n += len(starts)
                    if pend_n >= flush_windows:
                        _flush()
            _flush()
            return out


def pretrain_corpus(traces: List[Trace], cfg: model_lib.PredictorConfig,
                    vocab: DeltaVocab, cluster_key: str = "sm_warp",
                    distance: int = 30, steps: int = 300,
                    seed: int = 0):
    """Paper §7.1: build a corpus from several benchmarks (50% of each) and
    pretrain a single model on it.  The shared vocab must be built by the
    caller over the union of the traces."""
    import numpy as np
    xs, ys = [], []
    for tr in traces:
        half, _ = tr.split(0.5)
        ct = cluster_trace(half, cluster_key)
        data = build_dataset(ct, vocab, features=list(cfg.features),
                             distance=distance, max_train=8000, seed=seed)
        xs.append(data.x_train)
        ys.append(data.y_train)
    x = np.concatenate(xs)
    y = np.concatenate(ys)
    # reuse the dataset container for the trainer
    ds = dataclasses.replace(  # type: ignore[arg-type]
        data, x_train=x, y_train=y, x_valid=x[:256], y_valid=y[:256],
        x_test=x[:256], y_test=y[:256])
    res = train_predictor(cfg, ds, steps=steps, seed=seed)
    return res.params
