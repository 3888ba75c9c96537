"""Content-addressed cache of learned-prefetcher prediction arrays.

The learned sweep cells are the expensive ones: training the jax predictor
service dominates a (trace × prediction_us × device_frac) grid if every cell
retrains from scratch, even though the ``predict_trace`` output depends only
on the trace content and the predictor configuration — not on the replay
knobs (``prediction_us``, capacity) the grid actually varies.

This module gives those cells train-once semantics:

* Keys are **content-addressed**: sha256 over the trace's access records +
  instruction count plus every :class:`~repro.core.service.PredictorService`
  field that influences the predictions (cluster key, prediction distance,
  min-prob gate, sequence length, training steps, batch size, quantization,
  bypass threshold, seed, and the model identity: the ``model_family``
  name plus the architecture digest of its resolved
  :class:`~repro.core.families.PredictorConfig`) and a cache-format
  version.  Two callers holding bit-identical traces and configs always
  agree on the key, no matter how the trace was produced (generator, npz
  cache, in-process fixture) — and two model families on the same trace
  can never cross-serve one cached array.  The trace fingerprint is
  memoized on the trace instance *and the access array is frozen*
  (``writeable=False``) at memo time, so a later in-place mutation raises
  instead of silently reusing a stale fingerprint.
* Values are single-file ``.npz`` archives carrying the predictions array
  **plus its sha256** (over dtype+shape+bytes), written via **atomic
  write-rename** (``os.replace`` of a same-directory tempfile), so
  concurrent ``--workers`` processes can never observe a torn file, and
  out-of-band corruption (truncation, bit flips) is *detected* on read:
  a failing entry is quarantined to ``<entry>.corrupt`` with a warning
  and the key retrains — corrupt bytes are never served as predictions.
* A best-effort **training lock** — a crash-reclaimable lease file from
  :mod:`repro.distributed.fault_tolerance` — makes concurrent misses on
  the same key wait for the first trainer's result instead of training N
  times.  A lock whose owner pid is dead (SIGKILLed trainer on this
  host) or whose TTL expired is stolen immediately; a holder that
  finished but wrote a *corrupt* entry is detected by the waiters'
  checksummed polls (quarantine + immediate steal + retrain — no
  patience burned on an array that can never appear); a live-but-wedged
  holder is waited out for ``lock_patience_s`` and then overridden
  (correctness never depends on the lock).
* A per-process memo keeps the same array shared in-process even with no
  ``cache_dir`` (serial sweeps train once per (trace, model) pair too).

Set ``REPRO_PREDCACHE=0`` to disable all caching (the retrain-per-cell
baseline, used by the regression test in ``tests/test_sweep.py``).
"""
from __future__ import annotations

import hashlib
import json
import os
import tempfile
import time
import warnings
import zipfile
from typing import Dict, Optional

import numpy as np

from repro.distributed import fault_tolerance as ft
from repro.uvm import faults

#: bump on any change to the key schema, the stored array semantics, or the
#: prediction pipeline itself — stale arrays must never be served
#: (2: checksummed .npz entries with an embedded sha256;
#:  3: model identity in the key — ``model_family`` + resolved
#:  PredictorConfig digest, so no two architectures share an entry)
PREDCACHE_VERSION = 3

#: conventional subdirectory name under a sweep's trace cache
DEFAULT_SUBDIR = "pred_cache"

#: PredictorService fields that determine the predictions array.
#: ``model_config`` is the service's architecture-digest property
#: (repro.core.families.config_digest of the resolved family config):
#: without it, two families — or two revisions of one family's block —
#: on the same trace would collide on one cached array.
SERVICE_KEY_FIELDS = ("cluster_key", "distance", "min_prob", "seq_len",
                      "steps", "batch_size", "quantize", "bypass_threshold",
                      "seed", "model_family", "model_config")

_MEMO: Dict[str, np.ndarray] = {}


def clear_memo() -> None:
    """Drop the in-process memo (tests)."""
    _MEMO.clear()


def enabled() -> bool:
    return os.environ.get("REPRO_PREDCACHE", "1") != "0"


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------

def trace_content_key(trace) -> str:
    """Identity of a trace as the predictor sees it: the raw access records
    plus the instruction count (which scales the timing model, not the
    predictions, but keeps the key an honest trace fingerprint).  The hash
    is memoized on the trace instance — a grid calls this once per cell,
    and the access array is multi-MB at full scale.  Memoizing is only
    sound if the hashed bytes cannot change afterwards, so the access
    array is frozen (``writeable=False``) at memo time: an in-place
    mutation after keying then raises at the mutation site instead of
    silently serving another trace's predictions."""
    key = getattr(trace, "_predcache_content_key", None)
    if key is not None:
        return key
    acc = np.ascontiguousarray(trace.accesses)
    h = hashlib.sha256()
    h.update(str(acc.dtype).encode())
    h.update(str(acc.shape).encode())
    h.update(acc.tobytes())
    h.update(str(int(trace.n_instructions)).encode())
    key = h.hexdigest()[:24]
    try:
        trace.accesses.flags.writeable = False
        trace._predcache_content_key = key
    except (AttributeError, ValueError):
        # slots/frozen trace, or an accesses view we cannot freeze: skip
        # the memo and recompute per call — correct, just slower
        pass
    return key


def predictions_key(trace, **service_fields) -> str:
    """Cache key for one (trace content, predictor config) pair."""
    blob = json.dumps({"_v": PREDCACHE_VERSION,
                       "trace": trace_content_key(trace),
                       **service_fields}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:24]


# ---------------------------------------------------------------------------
# storage (atomic)
# ---------------------------------------------------------------------------

def _path(cache_dir: str, key: str) -> str:
    return os.path.join(cache_dir, f"preds_{key}.npz")


def _preds_digest(preds: np.ndarray) -> str:
    h = hashlib.sha256()
    h.update(str(preds.dtype).encode())
    h.update(str(preds.shape).encode())
    h.update(np.ascontiguousarray(preds).tobytes())
    return h.hexdigest()


def _quarantine(path: str, reason: str) -> None:
    warnings.warn(f"{reason}: quarantining {path} -> {path}.corrupt and "
                  "retraining", RuntimeWarning)
    try:
        os.replace(path, path + ".corrupt")
    except OSError:
        pass


def load_checked(cache_dir: str, key: str
                 ) -> "tuple[Optional[np.ndarray], bool]":
    """Load a cached predictions array; returns ``(array_or_None,
    corrupt)``.  The embedded sha256 is verified against the array bytes:
    an unreadable or checksum-failing entry (truncation, bit flips —
    anything the atomic rename cannot rule out) is quarantined to
    ``<entry>.corrupt`` and reads as a miss with ``corrupt=True``, so
    corruption triggers a retrain instead of silently skewing every
    downstream hit-rate.  The corrupt flag matters to lock *waiters*: a
    corrupt entry proves the holder already finished (and failed) its
    write, so waiting out its lease cannot produce a good array."""
    path = _path(cache_dir, key)
    try:
        with np.load(path, allow_pickle=False) as z:
            preds = np.ascontiguousarray(z["preds"])
            sha = str(z["sha"])
    except (FileNotFoundError, NotADirectoryError):
        return None, False
    except (ValueError, EOFError, OSError, KeyError, zipfile.BadZipFile):
        _quarantine(path, "unreadable prediction cache entry")
        return None, True
    if sha != _preds_digest(preds):
        _quarantine(path, "prediction cache checksum mismatch")
        return None, True
    preds.flags.writeable = False
    return preds, False


def load(cache_dir: str, key: str) -> Optional[np.ndarray]:
    """:func:`load_checked` without the corrupt flag."""
    return load_checked(cache_dir, key)[0]


def _count(name: str) -> None:
    """:func:`repro.obs.count`, imported here: keys and storage must work
    without pulling in jax."""
    from repro import obs
    obs.count(name)


def store(cache_dir: str, key: str, preds: np.ndarray) -> str:
    """Atomically persist a predictions array with its checksum: write a
    single ``.npz`` (array + sha256) to a same-directory tempfile, then
    ``os.replace`` onto the final name.  Concurrent writers race benignly
    — last rename wins, readers never see a partial file — and keeping
    array and checksum in one file means no writer interleaving can pair
    an array with another writer's checksum."""
    os.makedirs(cache_dir, exist_ok=True)
    path = _path(cache_dir, key)
    arr = np.ascontiguousarray(preds)
    fd, tmp = tempfile.mkstemp(dir=cache_dir, prefix=f".{key}.",
                               suffix=".tmp.npz")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, preds=arr, sha=np.array(_preds_digest(arr)))
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    faults.corrupt("pred.artifact", path, key)
    _count("predcache.stores")
    return path


# ---------------------------------------------------------------------------
# training lock (best effort, crash-reclaimable)
# ---------------------------------------------------------------------------

def _try_lock(lock_path: str, ttl_s: float) -> bool:
    """Claim the training lock for a key.  The lock is a lease file
    ({pid, host, ts}): a holder that was SIGKILLed on this host is stolen
    immediately via the dead-pid check, a holder elsewhere is presumed
    dead once its TTL expires — so one crashed trainer can never make
    every future cold-cache process serve its full ``lock_patience_s``.
    Legacy bare-pid lockfiles parse as TTL-less records and read as
    stale."""
    return ft.try_acquire_lease(lock_path, ttl_s,
                                extra={"role": "predcache-train"})


def _unlock(lock_path: str) -> None:
    ft.release_lease(lock_path)


# ---------------------------------------------------------------------------
# the train-once entry point
# ---------------------------------------------------------------------------

def get_or_train(trace, *, steps: int = 150, seed: int = 0,
                 cache_dir: Optional[str] = None,
                 service_kwargs: Optional[Dict] = None,
                 lock_poll_s: float = 0.25,
                 lock_patience_s: float = 900.0) -> np.ndarray:
    """Return the ``predict_trace`` array for (trace, predictor config),
    training at most once per key across the memo, the disk cache, and —
    via the lock — concurrent worker processes."""
    # lazy import: keys and storage must work without pulling in jax
    from repro.core import PredictorService

    def _fresh_service() -> "PredictorService":
        return PredictorService(steps=steps, seed=seed,
                                **(service_kwargs or {}))

    trained: list = []                   # _train ran in this call

    def _train() -> np.ndarray:
        from repro import compile_cache
        from repro.uvm.replay_core import require_device
        require_device("predictor training")
        _count("predcache.misses")
        trained.append(True)
        compile_cache.enable()
        svc = _fresh_service()
        svc.fit(trace)
        preds = np.ascontiguousarray(svc.predict_trace(), dtype=np.int64)
        preds.flags.writeable = False
        return preds

    if not enabled():
        return _train()

    probe = _fresh_service()
    fields = {f: getattr(probe, f) for f in SERVICE_KEY_FIELDS}
    key = predictions_key(trace, **fields)
    preds = _MEMO.get(key)
    if preds is not None:
        _count("predcache.hits")
        return preds

    if cache_dir is None:
        preds = _train()
        _MEMO[key] = preds
        return preds

    preds, corrupt = load_checked(cache_dir, key)
    if preds is None:
        os.makedirs(cache_dir, exist_ok=True)
        lock = _path(cache_dir, key) + ".lock"
        got = _try_lock(lock, lock_patience_s)
        if not got and corrupt:
            # A corrupt entry under someone else's live lock means its
            # holder already trained, stored, and failed (the entry is
            # quarantined): waiting out the lease can never produce a
            # good array, so steal it and retrain now.  If the entry was
            # a *previous* crash's debris and the current holder is
            # healthy, the steal costs one benign duplicate training run
            # (deterministic, atomic rename — last writer wins).
            _unlock(lock)
            got = _try_lock(lock, lock_patience_s)
        if not got:
            # another *live* process is training this key: wait for its
            # array.  Each poll re-probes the lease, so a holder that
            # dies mid-training is reclaimed at the next poll instead of
            # costing the full patience window.
            deadline = time.monotonic() + lock_patience_s
            while time.monotonic() < deadline:
                preds, corrupt = load_checked(cache_dir, key)
                if preds is not None:
                    break
                if corrupt:
                    # The holder already wrote its entry and the bytes
                    # are bad (now quarantined): it trained, stored, and
                    # failed — whether it is still alive, waiting out
                    # its lease can never yield a good array.  Steal the
                    # lock and retrain now instead of burning the full
                    # patience window.
                    _unlock(lock)
                    got = _try_lock(lock, lock_patience_s)
                    break
                if _try_lock(lock, lock_patience_s):
                    got = True           # holder released, died, or TTL'd
                    break
                time.sleep(lock_poll_s)
            if preds is None and not got:
                # patience exhausted: the lock holder is alive but wedged.
                # Steal the lock so it cannot poison this key for every
                # future cold-cache process; a benign duplicate training
                # run (deterministic, atomic rename) is the worst case.
                _unlock(lock)
                got = _try_lock(lock, lock_patience_s)
        if preds is None:
            try:
                preds = load(cache_dir, key)   # double-check under the lock
                if preds is None:
                    preds = _train()
                    store(cache_dir, key, preds)
            finally:
                if got:
                    _unlock(lock)
    if not trained:
        _count("predcache.hits")
    _MEMO[key] = preds
    return preds
