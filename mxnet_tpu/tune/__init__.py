"""Search-based Pallas autotuner with persistent cost tables.

The two Pallas kernel families (flash attention, fused LayerNorm)
pick their block shapes with hand-derived min()-clamp
heuristics tuned once for v5e defaults.  This package replaces "tuned
once" with the TVM recipe (arxiv 1802.04799): enumerate a small config
space, prune it through the kernels' own static VMEM predicate, time
the survivors, and persist the winner in an on-disk cost table keyed
like the jit cache — (family, shape, dtype, chip, schema).

Dispatch contract (``attention_dispatch`` and the layernorm block picker
consult :func:`table_config` first):

* **default mode measures nothing** — no table on disk and
  ``MXNET_AUTOTUNE`` unset means one dict miss and the pre-existing
  heuristic, bit-identical to the un-tuned dispatch (regression-
  tested);
* a **table hit** serves the stored config after re-validating it
  against the VMEM predicate (an invalid/corrupt entry falls back to
  the heuristic, never raises);
* ``MXNET_AUTOTUNE=1`` opts into **on-miss search** at dispatch time
  under a strict trial budget (``MXNET_AUTOTUNE_TRIALS``, default 6
  candidates x ``MXNET_AUTOTUNE_CALLS`` timed calls), and the result
  is persisted so every later process starts warm.

Offline: ``python -m mxnet_tpu.tune --family attention --shape
512:512:64`` searches without touching any training job.  Telemetry:
``autotune.hit|miss|search|fallback`` counters plus one ``autotune``
journal event per decision (the census ``tools/parse_log.py --jsonl``
renders).
"""
from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

from .. import context as _context
from . import cost_table, search
from .cost_table import (CostTable, FAMILY_FIELDS, KERNEL_FAMILIES,
                         SCHEMA_VERSION, canon_dtype, canon_shape,
                         baked_table_path, default_table_path,
                         platform_id)

__all__ = ["CostTable", "table_config", "table_blocks", "model_blocks",
           "program_knobs", "table_path", "autotune_enabled",
           "get_table", "default_table_path", "baked_table_path",
           "platform_id", "search", "cost_table", "model", "program"]

_TABLE = {"instance": None}
# instances whose on-miss search already failed this process: retraces
# and sibling call sites fall straight back to the heuristic instead of
# re-paying a full measured search that cannot be cached on disk
_FAILED_SEARCHES = set()


def get_table() -> CostTable:
    """Process-level table singleton (path fixed at first use), layered
    over the shipped read-only baked table when one exists for this
    platform (see :func:`cost_table.baked_table_path`)."""
    if _TABLE["instance"] is None:
        _TABLE["instance"] = CostTable(default_table_path(),
                                       baked=baked_table_path())
    return _TABLE["instance"]


def table_path() -> str:
    return get_table().path


def autotune_enabled() -> bool:
    """``MXNET_AUTOTUNE=1`` opts into on-miss measured search at
    dispatch time (trace time).  Off by default: steady-state dispatch
    must never measure.  Falsy spellings are case-insensitive —
    ``False``/``OFF``/``no`` must not silently enable measuring."""
    val = os.environ.get("MXNET_AUTOTUNE", "0").strip().lower()
    return val not in ("0", "false", "off", "no", "")


def _search_allowed() -> bool:
    # on-miss search compiles and times real kernels; off-TPU that means
    # interpret mode, which only the offline CLI opts into explicitly
    return autotune_enabled() and (
        _context.on_tpu()
        or os.environ.get("MXNET_AUTOTUNE_INTERPRET", "0") == "1")


def _budget(name: str, default: int) -> int:
    try:
        return max(1, int(os.environ.get(name, default)))
    except ValueError:
        return default


def table_config(family: str, shape: Sequence[int], dtype,
                 quiet: bool = False) -> Optional[dict]:
    """The tuned config for one (family, shape, dtype) instance, or
    None (→ caller uses its heuristic).

    Resolution order: in-memory/on-disk table (re-validated through the
    kernels' VMEM predicate), then — only when ``MXNET_AUTOTUNE`` opts
    in — an on-miss measured search under the strict trial budget whose
    winner is persisted.  Returns ``{**config, "source":
    "table"|"searched"}``.  Emits autotune.hit/miss/search/fallback
    counters and one ``autotune`` journal event per decision.

    ``quiet=True`` is the side-effect-free spelling for SECONDARY
    lookups of a decision already censused (the custom-vjp backward
    re-reading the forward's blocks): pure table lookup + validation,
    no counters, no journal, never a search."""
    from .. import telemetry
    shape = canon_shape(shape)
    dt = canon_dtype(dtype, family)
    rec = get_table().lookup(family, shape, dt)
    if quiet:
        if rec is not None and search.valid_config(family, shape, dt,
                                                   rec["config"]):
            return dict(rec["config"], source="table")
        return None
    if rec is not None:
        cfg = rec["config"]
        if search.valid_config(family, shape, dt, cfg):
            telemetry.inc("autotune.hit")
            telemetry.event("autotune", "hit", family=family,
                            shape=list(shape), dtype=dt, config=cfg)
            return dict(cfg, source="table")
        # stored config no longer satisfies the kernels' own clamp
        # (e.g. a table baked before a budget change): count the
        # fallback loudly, then fall THROUGH — with search enabled the
        # stale record is re-tuned and overwritten, not pinned
        telemetry.inc("autotune.fallback")
        telemetry.event("autotune", "fallback", family=family,
                        shape=list(shape), dtype=dt, config=cfg,
                        reason="invalid_table_config")
    if family in KERNEL_FAMILIES and _search_allowed() \
            and (family, shape, dt) not in _FAILED_SEARCHES:
        res = _dispatch_search(family, shape, dt)
        if res is not None:
            telemetry.inc("autotune.search")
            telemetry.event("autotune", "search", family=family,
                            shape=list(shape), dtype=dt,
                            config=res["config"],
                            ms=res["best_ms"], trials=res["trials"],
                            interpret=res.get("interpret", False),
                            ranked=res.get("ranked", False))
            return dict(res["config"], source="searched")
        _FAILED_SEARCHES.add((family, shape, dt))
        if rec is None:
            # one fallback event per DECISION: an invalid entry was
            # already counted above, only a search-on-true-miss failure
            # is new information
            telemetry.inc("autotune.fallback")
            telemetry.event("autotune", "fallback", family=family,
                            shape=list(shape), dtype=dt,
                            reason="search_failed")
        return None
    if rec is None:
        # only an absent entry is a "miss"; an invalid one was already
        # counted as a fallback above
        telemetry.inc("autotune.miss")
        telemetry.event("autotune", "miss", family=family,
                        shape=list(shape), dtype=dt)
    return None


def _dispatch_search(family, shape, dt):
    """On-miss search at dispatch time: strict budget, result persisted
    (best-effort — an unwritable table still returns the config).

    v2: the search is model-ranked when the learned cost model is
    usable — same budget knob, but only the top-K predicted candidates
    get timed.  An untrained/over-CV model counts one
    ``autotune.model_fallback`` and the search degrades to v1's
    log-distance order, bit-identically."""
    from .. import telemetry
    from . import model as _model
    interp = os.environ.get("MXNET_AUTOTUNE_INTERPRET", "0") == "1" \
        and not _context.on_tpu()
    cm = None
    if _model.model_enabled():
        try:
            cm = _model.get_model(family)
        except Exception:
            cm = None
        if cm is None:
            telemetry.inc("autotune.model_fallback")
            telemetry.event("autotune", "model_fallback", family=family,
                            shape=list(shape), dtype=dt,
                            reason="untrained_or_cv")
    res = search.search_config(
        family, shape, dt,
        trials=_budget("MXNET_AUTOTUNE_TRIALS", search.DEFAULT_TRIALS),
        calls=_budget("MXNET_AUTOTUNE_CALLS", search.DEFAULT_CALLS),
        interpret=interp, model=cm)
    if res is None:
        return None
    try:
        get_table().record(family, shape, dt, res["config"],
                           best_ms=res["best_ms"], source="searched",
                           trials=res["trials"],
                           interpret=res.get("interpret", False),
                           results=res.get("results"))
    except OSError:
        pass
    return res


def table_blocks(family: str, shape: Sequence[int], dtype,
                 default: Optional[Tuple[int, ...]] = None,
                 quiet: bool = False):
    """Tuned blocks as a tuple in the family's field order (attention →
    ``(block_q, block_k)``), or ``default`` on a miss.

    This is the direct-consumer spelling (`bq, bk = table_blocks(...,
    default=(1024, 2048))`): graftlint's static pallas estimator
    resolves the ``default=`` literal as the config it sizes the
    kernel's VMEM working set at, so tune-table call sites stay inside
    the ``pallas-vmem-budget`` rule's reach.  ``quiet=True`` marks a
    SECONDARY lookup of an already-censused decision (a kernel's bwd
    re-reading the fwd's blocks): no counters/journal, never a
    search."""
    cfg = table_config(family, shape, dtype, quiet=quiet)
    if cfg is None:
        return default
    out = tuple(cfg[f] for f in FAMILY_FIELDS[family])
    return out if len(out) > 1 else out[0]


def model_config(family: str, shape: Sequence[int], dtype,
                 quiet: bool = False) -> Optional[dict]:
    """:func:`table_config` plus the learned-model fallback: on a true
    miss where on-miss search is not possible (off-TPU without the
    interpret opt-in, or a search that failed) but ``MXNET_AUTOTUNE``
    is on and the cost model is usable, serve the predicted-fastest
    VALID candidate with ``source="model"`` (counter
    ``autotune.model_hit``).  The model leg stays behind the SAME env
    gate as search — default mode still resolves heuristic,
    bit-identically — and only ever picks from the statically-pruned
    candidate grid, so it cannot emit a config the VMEM predicate (or
    graftlint) would reject."""
    cfg = table_config(family, shape, dtype, quiet=quiet)
    if cfg is not None:
        return cfg
    if family not in KERNEL_FAMILIES or not autotune_enabled():
        return None
    from . import model as _model
    try:
        cm = _model.get_model(family)
    except Exception:
        cm = None
    if cm is None:
        return None
    shape = canon_shape(shape)
    dt = canon_dtype(dtype, family)
    try:
        cands = search.candidates(family, shape, dt)
        best = min(cands, key=lambda c: (cm.predict_config_ms(shape, dt,
                                                              c),
                                         tuple(sorted(c.items()))))
    except Exception:
        return None
    if not quiet:
        from .. import telemetry
        telemetry.inc("autotune.model_hit")
        telemetry.event("autotune", "model_pick", family=family,
                        shape=list(shape), dtype=dt, config=best,
                        cv_error=cm.cv_error, n_samples=cm.n_samples)
    return dict(best, source="model")


def model_blocks(family: str, shape: Sequence[int], dtype,
                 default: Optional[Tuple[int, ...]] = None,
                 quiet: bool = False):
    """:func:`table_blocks` with the learned-model fallback of
    :func:`model_config` — same tuple contract, same ``default=``
    literal that graftlint's static pallas estimator resolves (the
    checker folds ``model_blocks`` exactly like ``table_blocks``)."""
    cfg = model_config(family, shape, dtype, quiet=quiet)
    if cfg is None:
        return default
    out = tuple(cfg[f] for f in FAMILY_FIELDS[family])
    return out if len(out) > 1 else out[0]


def program_knobs(family, shape, default=None, quiet=False):
    """Tuned program-level schedule knobs (see :mod:`tune.program`) —
    re-exported here so consumers and graftlint resolve one spelling."""
    from . import program
    return program.program_knobs(family, shape, default=default,
                                 quiet=quiet)


def _reset_for_tests():
    """Forget the table singleton, failed-search memo, trained models
    and platform id (tests repoint MXNET_AUTOTUNE_TABLE between
    cases)."""
    from . import model as _model
    _TABLE["instance"] = None
    _FAILED_SEARCHES.clear()
    _model._reset_for_tests()
    cost_table._reset_platform_cache()
