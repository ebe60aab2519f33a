"""Learned cost model for the autotuner (v2 of the TVM recipe, arxiv
1802.04799): measured search beats heuristics but pays a timing cost
per candidate, so a small regression model trained on the timings we
ALREADY persist (per-candidate ``results`` in the cost-table records,
plus ``autotune`` search events in telemetry JSONL journals) ranks the
candidate grid by predicted time and only the top-K predictions are
ever measured.

Deliberately boring machinery — stdlib + NumPy only:

* **features** (:func:`featurize`) are the quantities the kernels' own
  sizing arithmetic is written in: log2 of every shape dim and config
  field, the dtype itemsize, the kernel's static VMEM working set
  (``search.config_vmem_bytes`` — the same expression graftlint folds),
  and per-block grid/work counts.  Program-level families (``prog_*``)
  featurize generically on shape + knob values, so ONE mechanism
  covers Pallas blocks and whole-program schedule knobs.
* **model**: ridge regression on ``log(ms)`` via normal equations
  (:class:`CostModel.fit` — a closed-form ``numpy.linalg.solve``, no
  iterative optimizer, bit-deterministic for a fixed seed).  k-fold
  cross-validation is part of ``fit``: ``cv_error`` (mean absolute
  relative error in linear space) is the model's own honesty metric.
* **hard fallback**: :attr:`CostModel.usable` gates every consumer —
  an untrained model (fewer than ``MIN_SAMPLES`` samples) or one whose
  ``cv_error`` exceeds ``MXNET_AUTOTUNE_MODEL_CV`` (default 0.5) is
  refused, and the search falls back to v1's log-distance ordering.
  A model can therefore never make tuning WORSE than v1: it only
  reorders which candidates get measured first.

Training-data hygiene: interpret-mode timings (functional smoke runs
off-TPU) are excluded on a real chip — the same provenance rule
``cost_table.CostTable.lookup`` applies to whole records.
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

from .. import context as _context

MODEL_SCHEMA = 1
# below this many samples a model is untrained by definition; the
# normal-equation fit is exact, so the floor only guards generalization
MIN_SAMPLES = 8
_DEF_CV_MAX = 0.5          # mean |pred/measured - 1| gate
_DEF_FOLDS = 4
_RIDGE_LAMBDA = 1e-3


def _cv_threshold() -> float:
    try:
        return float(os.environ.get("MXNET_AUTOTUNE_MODEL_CV",
                                    _DEF_CV_MAX))
    except ValueError:
        return _DEF_CV_MAX


def model_enabled() -> bool:
    """``MXNET_AUTOTUNE_MODEL`` kill switch (default ON — the model only
    reorders what an already-opted-in search measures; falsy spellings
    match ``autotune_enabled``'s)."""
    val = os.environ.get("MXNET_AUTOTUNE_MODEL", "1").strip().lower()
    return val not in ("0", "false", "off", "no", "")


def _log2(x) -> float:
    return math.log2(max(1.0, float(x)))


def featurize(family: str, shape: Sequence[int], dtype,
              config: Dict[str, int]) -> List[float]:
    """Feature vector for one (instance, candidate config) pair.

    Width is fixed PER FAMILY (models are per-family), and every
    feature is a smooth function of quantities known before any
    compile: shape dims, config fields, dtype width, and the kernels'
    own VMEM arithmetic."""
    from . import cost_table as ct
    from . import search as se

    fields = ct.FAMILY_FIELDS[family]
    shape = [int(d) for d in shape]
    cfg = [int(config[f]) for f in fields]
    try:
        import numpy as onp
        itemsize = float(onp.dtype(str(dtype)).itemsize)
    except Exception:
        itemsize = 2.0
    feats = [_log2(d) for d in shape]
    feats += [_log2(v) for v in cfg]
    feats.append(itemsize)
    # total-work proxy: product of shape dims (log-space)
    feats.append(sum(_log2(d) for d in shape))
    vmem = se.config_vmem_bytes(family, shape, dtype, config)
    feats.append(_log2(vmem) if vmem else 0.0)
    # per-config grid/occupancy terms: how many blocks tile each axis
    # (the dispatch/streaming counts the measured time scales with)
    for d, v in zip(shape, cfg):
        feats.append(_log2(-(-d // max(1, v))))
    return feats


class CostModel:
    """Ridge regression on ``log(ms)`` with built-in k-fold CV.

    ``fit`` is closed-form and deterministic for a fixed ``seed`` (the
    seed only drives the CV fold shuffle).  ``predict_ms`` returns
    linear-space milliseconds."""

    def __init__(self, family: str):
        self.family = family
        self.weights: Optional[List[float]] = None
        self.x_mean: Optional[List[float]] = None
        self.x_scale: Optional[List[float]] = None
        self.cv_error: Optional[float] = None
        self.n_samples = 0

    # -- training --------------------------------------------------------
    def _design(self, X, onp):
        Xn = (onp.asarray(X, "float64") - self.x_mean) / self.x_scale
        return onp.concatenate(
            [onp.ones((Xn.shape[0], 1)), Xn], axis=1)

    @staticmethod
    def _solve(A, y, onp):
        n = A.shape[1]
        reg = _RIDGE_LAMBDA * onp.eye(n)
        reg[0, 0] = 0.0          # never shrink the bias
        return onp.linalg.solve(A.T @ A + reg, A.T @ y)

    def fit(self, samples: Sequence[Tuple[Sequence[float], float]],
            seed: int = 0, folds: int = _DEF_FOLDS) -> "CostModel":
        """Fit on ``(features, ms)`` pairs and cross-validate.

        Deterministic: same samples + same seed -> bitwise-identical
        weights and ``cv_error`` (regression-tested)."""
        import numpy as onp
        samples = [(list(f), float(ms)) for f, ms in samples
                   if ms > 0.0 and all(math.isfinite(v) for v in f)]
        self.n_samples = len(samples)
        if len(samples) < MIN_SAMPLES:
            self.weights = None
            self.cv_error = None
            return self
        X = onp.asarray([f for f, _ in samples], "float64")
        y = onp.log(onp.asarray([ms for _, ms in samples], "float64"))
        self.x_mean = X.mean(axis=0)
        scale = X.std(axis=0)
        scale[scale < 1e-9] = 1.0
        self.x_scale = scale
        A = self._design(X, onp)
        # k-fold CV first (on the same normalization — a tiny optimism
        # bias, irrelevant at the 50% error gate this feeds)
        k = max(2, min(folds, len(samples) // 2))
        idx = onp.arange(len(samples))
        onp.random.RandomState(seed).shuffle(idx)
        errs = []
        for f in range(k):
            test = idx[f::k]
            train = onp.setdiff1d(idx, test)
            w = self._solve(A[train], y[train], onp)
            pred = onp.exp(A[test] @ w)
            meas = onp.exp(y[test])
            errs.extend(onp.abs(pred / meas - 1.0).tolist())
        self.cv_error = float(onp.mean(errs)) if errs else None
        self.weights = self._solve(A, y, onp).tolist()
        self.x_mean = self.x_mean.tolist()
        self.x_scale = self.x_scale.tolist()
        return self

    # -- inference -------------------------------------------------------
    @property
    def trained(self) -> bool:
        return self.weights is not None

    @property
    def usable(self) -> bool:
        """Trained AND honest: cross-validation error within the
        ``MXNET_AUTOTUNE_MODEL_CV`` gate.  Every consumer checks this —
        an overconfident model must lose to the v1 ordering, not race
        it."""
        return self.trained and self.cv_error is not None \
            and self.cv_error <= _cv_threshold()

    def predict_ms(self, features: Sequence[float]) -> float:
        if not self.trained:
            raise RuntimeError("CostModel(%s) is untrained" % self.family)
        import numpy as onp
        A = self._design(onp.asarray([list(features)]), onp)
        return float(onp.exp(A @ onp.asarray(self.weights))[0])

    def predict_config_ms(self, shape, dtype, config) -> float:
        return self.predict_ms(featurize(self.family, shape, dtype,
                                         config))

    # -- (de)serialization ----------------------------------------------
    def to_dict(self) -> dict:
        return {"schema": MODEL_SCHEMA, "family": self.family,
                "weights": self.weights, "x_mean": self.x_mean,
                "x_scale": self.x_scale, "cv_error": self.cv_error,
                "n_samples": self.n_samples}

    @classmethod
    def from_dict(cls, d: dict) -> "CostModel":
        if not isinstance(d, dict) or d.get("schema") != MODEL_SCHEMA:
            raise ValueError("unknown cost-model schema: %r"
                             % (d.get("schema") if isinstance(d, dict)
                                else d,))
        m = cls(str(d["family"]))
        m.weights = d.get("weights")
        m.x_mean = d.get("x_mean")
        m.x_scale = d.get("x_scale")
        m.cv_error = d.get("cv_error")
        m.n_samples = int(d.get("n_samples") or 0)
        return m


# ---------------------------------------------------------------------------
# training-data assembly (cost-table records + telemetry JSONL journals)
# ---------------------------------------------------------------------------

def _sample_ok(shape, cfg, ms, fields) -> bool:
    try:
        return (isinstance(cfg, dict)
                and all(int(cfg[f]) > 0 for f in fields)
                and float(ms) > 0.0
                and all(int(d) > 0 for d in shape))
    except (KeyError, TypeError, ValueError):
        return False


def training_samples(table, family: str,
                     include_interpret: Optional[bool] = None,
                     journal: Optional[str] = None):
    """``(features, ms)`` pairs for one family from a
    :class:`cost_table.CostTable` plus (optionally) a telemetry JSONL
    journal.

    Every timed candidate in a record's ``results`` list is a sample
    (the search pays for those timings once; the model is how they
    compound), the winner's ``best_ms`` is one more, and ``autotune``
    search events in the journal contribute their measured winners.
    Interpret-mode records are EXCLUDED on a real chip
    (``include_interpret`` defaults to "only off-TPU") — smoke timings
    must never teach a real chip's model.  Malformed records/lines are
    skipped, never raised: corrupt training data degrades to an
    untrained model, which every consumer already survives."""
    from . import cost_table as ct

    if include_interpret is None:
        include_interpret = not _context.on_tpu()
    fields = ct.FAMILY_FIELDS.get(family)
    if fields is None:
        return []
    out = []

    def add(shape, dtype, cfg, ms):
        if not _sample_ok(shape, cfg, ms, fields):
            return
        try:
            out.append((featurize(family, shape, dtype, cfg), float(ms)))
        except Exception:
            pass

    for rec in (table.entries() if table is not None else []):
        if rec.get("family") != family:
            continue
        if rec.get("interpret") and not include_interpret:
            continue
        shape, dtype = rec.get("shape") or [], rec.get("dtype")
        for r in rec.get("results") or []:
            if isinstance(r, dict) and "ms" in r:
                add(shape, dtype, r.get("config"), r.get("ms"))
        if rec.get("best_ms") is not None and not rec.get("results"):
            add(shape, dtype, rec.get("config"), rec.get("best_ms"))
    for shape, dtype, cfg, ms, interp in _journal_samples(journal,
                                                         family):
        if interp and not include_interpret:
            continue
        add(shape, dtype, cfg, ms)
    return out


def _journal_samples(path: Optional[str], family: str):
    """Measured (shape, dtype, config, ms, interpret) tuples from the
    ``autotune`` search events of a telemetry JSONL export.  Tolerant:
    an unreadable file or unparsable line contributes nothing."""
    if not path:
        return
    try:
        with open(path) as fh:
            lines = fh.readlines()
    except (OSError, IOError):
        return
    for line in lines:
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if not isinstance(rec, dict) or rec.get("kind") != "autotune" \
                or rec.get("name") != "search" \
                or rec.get("family") != family:
            continue
        if rec.get("ms") is None:
            continue
        yield (rec.get("shape") or [], rec.get("dtype"),
               rec.get("config"), rec.get("ms"),
               bool(rec.get("interpret")))


# process-level model cache: retrained when the backing table changes
# (CostTable.generation moves on every record())
_MODELS: Dict[str, tuple] = {}


def get_model(family: str, table=None,
              journal: Optional[str] = None) -> Optional[CostModel]:
    """The process-level model for ``family``, trained lazily from the
    autotune table (plus ``MXNET_AUTOTUNE_SPANS`` — a telemetry JSONL
    journal — when set) and retrained whenever the table records a new
    entry.  Returns None when modeling is disabled or the fit is not
    :attr:`CostModel.usable` — callers treat None as "use the v1
    log-distance ordering"."""
    if not model_enabled():
        return None
    if table is None:
        from . import get_table
        table = get_table()
    journal = journal or os.environ.get("MXNET_AUTOTUNE_SPANS")
    gen = getattr(table, "generation", 0)
    cached = _MODELS.get(family)
    if cached is not None and cached[0] == (id(table), gen, journal):
        model = cached[1]
    else:
        model = CostModel(family).fit(
            training_samples(table, family, journal=journal))
        _MODELS[family] = ((id(table), gen, journal), model)
    return model if model.usable else None


def _reset_for_tests():
    _MODELS.clear()
