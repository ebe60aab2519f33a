"""Measured search over Pallas kernel configs (the TVM recipe, arxiv
1802.04799: enumerate a small schedule space, prune statically, time
the survivors, persist the winner).

This module is THE timing harness for kernel tuning — ``bench.py``'s
attention A/B leg and ``tools/attn_probe.py`` are thin layers over it,
and the offline CLI (``python -m mxnet_tpu.tune``) and the on-miss
dispatch search both call :func:`search_config`.

Candidate pruning REUSES the kernels' own sizing arithmetic —
``_blocks_fit`` (forward and backward VMEM budgets) from
``ops/pallas_attention`` and the ``_VMEM_BUDGET`` constant from
``ops/pallas_layernorm`` — the exact
expressions graftlint's static pallas estimator folds, so no invalid
candidate is ever timed and the static rule rejects anything the
search could not have emitted.

Determinism contract: candidate order is a pure function of the
instance, timing is injectable (``timer=``/``measure=``), and ties go
to the earliest candidate — a fake timer makes the whole search
reproducible bit-for-bit (tested).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence

__all__ = ["min_time", "fwd_bwd_loop", "candidates", "heuristic_config",
           "valid_config", "search_config", "measure_attention_config",
           "attention_loop", "compiled_cost", "config_vmem_bytes"]

# dispatch-time (on-miss) search budget: at most this many candidates
# are ever timed per instance unless the caller widens it
DEFAULT_TRIALS = 6
DEFAULT_CALLS = 3        # min-of-K measured calls per candidate
DEFAULT_WARMUP = 1       # discarded compile+warmup calls per candidate

# synthetic operand sizes for the attention measurement (enough rows to
# fill the grid; the offline CLI can override)
_ATTN_BATCH = 4
_ATTN_HEADS = 8
_ATTN_INNER = 4          # chained fwd+bwd iterations inside one jit

_BQ_CANDIDATES = (128, 256, 512, 1024, 2048)
_BK_CANDIDATES = (128, 256, 512, 1024, 2048)
_LN_ROW_CANDIDATES = (8, 16, 32, 64, 128, 256, 512, 1024)


def _block_ready(x):
    import jax
    for leaf in jax.tree_util.tree_leaves(x):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


def min_time(fn: Callable[[], object], calls: int = DEFAULT_CALLS,
             warmup: int = DEFAULT_WARMUP,
             timer: Optional[Callable[[], float]] = None) -> float:
    """Min-of-``calls`` seconds for ``fn()`` bounded by block_until_ready,
    after ``warmup`` discarded calls (compile + cache warm).  ``timer``
    is injectable for deterministic tests."""
    timer = timer or time.perf_counter
    for _ in range(warmup):
        _block_ready(fn())
    best = None
    for _ in range(max(1, calls)):
        t0 = timer()
        _block_ready(fn())
        dt = timer() - t0
        best = dt if best is None else min(best, dt)
    return best


def fwd_bwd_loop(fn, inner: int):
    """Jitted loop running ``inner`` chained fwd+bwd iterations of
    ``fn(q, k, v)`` (grads w.r.t. all three operands, data dependence
    between iterations) — kernel time, not dispatch time.  The one
    loop-builder shared by the search, bench.py's A/B leg and
    tools/attn_probe.py."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    grad = jax.grad(lambda q, k, v:
                    jnp.sum(fn(q, k, v).astype(jnp.float32)),
                    argnums=(0, 1, 2))

    @jax.jit
    def loop(q, k, v):
        def body(_, qkv):
            q, k, v = qkv
            dq, dk, dv = grad(q, k, v)
            return (q + 0 * dq, k + 0 * dk, v + 0 * dv)
        return lax.fori_loop(0, inner, body, (q, k, v))
    return loop


def _rup(x: int, m: int) -> int:
    return x + (-x) % m


def _log2(x: int) -> float:
    import math
    return math.log2(x)


# ---------------------------------------------------------------------------
# candidate spaces (heuristic config always first, order deterministic)
# ---------------------------------------------------------------------------

def heuristic_config(family: str, shape: Sequence[int],
                     dtype) -> Optional[Dict[str, int]]:
    """Today's hand-derived clamp config for an instance — the fallback
    the tuned config is benched against, always candidate #0."""
    if family == "attention":
        from ..ops.pallas_attention import tune_attention_blocks
        seq_q, seq_k, head_dim = shape
        bq, bk = tune_attention_blocks(seq_q, seq_k, head_dim, dtype)
        return {"block_q": bq, "block_k": bk}
    if family == "layernorm":
        from ..ops.pallas_layernorm import _pick_block_rows_heuristic
        rows, C = shape
        block = _pick_block_rows_heuristic(C)
        if block is None:
            return None
        return {"block_rows": block}
    raise ValueError("unknown kernel family %r" % (family,))


def valid_config(family: str, shape: Sequence[int], dtype,
                 config: Dict[str, int]) -> bool:
    """The kernels' own VMEM/clamp predicate — the same arithmetic the
    graftlint pallas estimator checks statically.  Table entries and
    search candidates both pass through here; an invalid config is a
    heuristic fallback, never a compile attempt."""
    if family.startswith("prog_"):
        # program-level knobs validate through their own module (no
        # VMEM arithmetic; range/shape checks instead)
        from . import program
        return program.valid_config(family, shape, config)
    try:
        if family == "attention":
            import jax.numpy as jnp
            from ..ops.pallas_attention import _blocks_fit, _LANES
            seq_q, seq_k, head_dim = shape
            bq, bk = int(config["block_q"]), int(config["block_k"])
            # sublane (8) / lane (128) alignment: Mosaic rejects
            # misaligned blocks at compile, so a hand-edited table
            # entry must fail HERE, not in the training job
            if bq < 8 or bq % 8 or bk < _LANES or bk % _LANES:
                return False
            Dp = head_dim + (-head_dim) % 64
            itemsize = jnp.dtype(dtype).itemsize
            return _blocks_fit(bq, bk, Dp, itemsize)
        if family == "layernorm":
            from ..ops.pallas_layernorm import _VMEM_BUDGET
            rows, C = shape
            b = int(config["block_rows"])
            return b >= 8 and b % 8 == 0 and 3 * 4 * b * C <= _VMEM_BUDGET
    except (KeyError, TypeError, ValueError):
        return False
    return False


def config_vmem_bytes(family: str, shape: Sequence[int], dtype,
                      config: Dict[str, int]) -> Optional[int]:
    """The kernel's own static VMEM working-set estimate for a config —
    the same arithmetic :func:`valid_config` prunes with and the
    graftlint pallas estimator folds — or None for families without one
    (program-level knobs).  The learned cost model's strongest feature:
    time tracks the working set long before it tracks block geometry."""
    try:
        if family == "attention":
            import jax.numpy as jnp
            from ..ops.pallas_attention import _fwd_vmem_bytes
            _, _, head_dim = shape
            Dp = head_dim + (-head_dim) % 64
            return int(_fwd_vmem_bytes(int(config["block_q"]),
                                       int(config["block_k"]), Dp,
                                       jnp.dtype(dtype).itemsize))
        if family == "layernorm":
            _, C = shape
            return 3 * 4 * int(config["block_rows"]) * int(C)
    except (KeyError, TypeError, ValueError):
        return None
    return None


def candidates(family: str, shape: Sequence[int],
               dtype) -> List[Dict[str, int]]:
    """Pruned candidate configs: the heuristic first, then the grid
    ordered by log-distance FROM the heuristic (ties by field values —
    fully deterministic).  The ordering is what makes a small trial
    budget meaningful: truncating to N keeps the heuristic's
    neighbourhood, not one corner of the grid.  Block sizes are clamped
    to the padded instance extents (a block larger than the axis only
    buys padding) and every survivor already honours the VMEM
    predicate."""
    heur = heuristic_config(family, shape, dtype)
    out: List[Dict[str, int]] = []
    seen = set()

    def add(cfg):
        if cfg is None:
            return
        key = tuple(sorted(cfg.items()))
        if key in seen or not valid_config(family, shape, dtype, cfg):
            return
        seen.add(key)
        out.append(cfg)

    def _log_dist(cfg):
        # halvings/doublings away from the heuristic across all fields
        if heur is None:
            return 0.0
        d = 0.0
        for f, v in cfg.items():
            h = heur.get(f, v)
            d += abs(_log2(max(1, int(v))) - _log2(max(1, int(h))))
        return d

    add(heur)
    grid: List[Dict[str, int]] = []
    if family == "attention":
        from ..ops.pallas_attention import _LANES
        seq_q, seq_k, _ = shape
        bqs = sorted({min(b, max(8, _rup(seq_q, 8)))
                      for b in _BQ_CANDIDATES})
        bks = sorted({min(b, _rup(seq_k, _LANES)) for b in _BK_CANDIDATES}
                     | {_rup(seq_k, _LANES)})
        grid = [{"block_q": bq, "block_k": bk}
                for bq in bqs for bk in bks]
    elif family == "layernorm":
        rows, _ = shape
        grid = [{"block_rows": b}
                for b in sorted({min(b, max(8, _rup(rows, 8)))
                                 for b in _LN_ROW_CANDIDATES})]
    else:
        raise ValueError("unknown kernel family %r" % (family,))
    for cfg in sorted(grid, key=lambda c: (_log_dist(c),
                                           tuple(sorted(c.items())))):
        add(cfg)
    return out


def attention_variant(seq_k: int, block_k: int) -> str:
    """Which forward kernel a (seq_k, block_k) pair routes to — the
    same rule attention_dispatch applies."""
    return "short_seq" if seq_k <= block_k else "streaming"


# ---------------------------------------------------------------------------
# measurement (per family)
# ---------------------------------------------------------------------------

def _rand_operands(shapes, dtype, seed=0):
    import numpy as onp
    import jax.numpy as jnp
    rs = onp.random.RandomState(seed)
    return tuple(jnp.asarray(rs.uniform(-1, 1, s).astype("float32"),
                             jnp.dtype(dtype)) for s in shapes)


def attention_loop(batch, heads, seq_q, seq_k, head_dim, dtype, config,
                   causal=False, inner=_ATTN_INNER, interpret=False):
    """(jitted fwd+bwd loop, (q, k, v)) for one explicit attention
    config — the flash kernels with ``config``'s blocks wired through a
    local custom_vjp so the default-block wrapper never re-tunes."""
    from ..ops import pallas_attention as pa
    import jax

    bq, bk = int(config["block_q"]), int(config["block_k"])

    @jax.custom_vjp
    def att(q, k, v):
        return pa.pallas_flash_attention(q, k, v, causal=causal,
                                         block_q=bq, block_k=bk,
                                         interpret=interpret)

    def att_fwd(q, k, v):
        out, lse = pa.pallas_flash_attention(q, k, v, causal=causal,
                                             block_q=bq, block_k=bk,
                                             interpret=interpret,
                                             return_lse=True)
        return out, (q, k, v, out, lse)

    def att_bwd(res, g):
        q, k, v, out, lse = res
        return pa.pallas_flash_attention_bwd(q, k, v, out, lse, g,
                                             causal=causal, block_q=bq,
                                             block_k=bk,
                                             interpret=interpret)

    att.defvjp(att_fwd, att_bwd)
    q, k, v = _rand_operands(((batch, heads, seq_q, head_dim),
                              (batch, heads, seq_k, head_dim),
                              (batch, heads, seq_k, head_dim)), dtype)
    return fwd_bwd_loop(att, inner), (q, k, v)


def measure_attention_config(batch, heads, seq_q, seq_k, head_dim, dtype,
                             config, causal=False, inner=_ATTN_INNER,
                             calls=DEFAULT_CALLS, warmup=DEFAULT_WARMUP,
                             timer=None, interpret=False):
    """Seconds per fwd+bwd iteration for one explicit config (min-of-
    ``calls``, ``inner`` chained iterations amortize dispatch)."""
    loop, args = attention_loop(batch, heads, seq_q, seq_k, head_dim,
                                dtype, config, causal=causal, inner=inner,
                                interpret=interpret)
    return min_time(lambda: loop(*args), calls=calls, warmup=warmup,
                    timer=timer) / max(1, inner)


def _measure_layernorm(shape, dtype, config, calls, warmup, timer,
                       interpret):
    import jax
    from ..ops import pallas_layernorm as ln

    rows, C = shape
    block = int(config["block_rows"])
    x, ct = _rand_operands(((rows, C),) * 2, dtype)
    g, b = _rand_operands(((C,),) * 2, "float32", seed=1)

    @jax.jit
    def step(x, g, b, ct):
        y, mu, rstd = ln.pallas_layer_norm_fwd(x, g, b, 1e-5,
                                               block_rows=block,
                                               interpret=interpret)
        dx, dg, db = ln.pallas_layer_norm_bwd(x, g, mu, rstd, ct,
                                              block_rows=block,
                                              interpret=interpret)
        return y, dx, dg, db

    return min_time(lambda: step(x, g, b, ct), calls=calls,
                    warmup=warmup, timer=timer)


def _measure_candidate(family, shape, dtype, config, calls=DEFAULT_CALLS,
                       warmup=DEFAULT_WARMUP, timer=None,
                       interpret=False):
    """Milliseconds for one candidate (module-level so tests can inject
    a fake).  Attention reports per-inner-iteration time; layernorm
    a full fwd+bwd pass."""
    if family == "attention":
        seq_q, seq_k, head_dim = shape
        s = measure_attention_config(_ATTN_BATCH, _ATTN_HEADS, seq_q,
                                     seq_k, head_dim, dtype, config,
                                     calls=calls, warmup=warmup,
                                     timer=timer, interpret=interpret)
    elif family == "layernorm":
        s = _measure_layernorm(shape, dtype, config, calls, warmup,
                               timer, interpret)
    else:
        raise ValueError("unknown kernel family %r" % (family,))
    return s * 1000.0


def model_top_k(budget: int) -> int:
    """How many candidates a model-ranked search actually times: half
    the v1 budget (``MXNET_AUTOTUNE_MODEL_TOPK`` overrides) — STRICTLY
    fewer than ``budget`` whenever the budget allows more than one, by
    the acceptance contract: the model's whole value is timing less."""
    import os
    try:
        k = int(os.environ.get("MXNET_AUTOTUNE_MODEL_TOPK", "0"))
    except ValueError:
        k = 0
    if k <= 0:
        k = max(1, int(budget) // 2)
    return max(1, min(k, int(budget)))


def search_config(family, shape, dtype, trials=DEFAULT_TRIALS,
                  calls=DEFAULT_CALLS, warmup=DEFAULT_WARMUP, timer=None,
                  measure=None, interpret=False, model=None, top_k=None):
    """Measured search for one instance.

    Enumerates :func:`candidates` (heuristic first), keeps the first
    ``trials`` (the STRICT budget for on-miss dispatch search), times
    each with min-of-``calls``, and returns::

        {"config": best, "best_ms": float, "source": "searched",
         "trials": n_actually_timed, "space": n_enumerated,
         "interpret": bool, "ranked": bool, "results": [...]}

    or None when nothing could be timed.  ``measure`` overrides the
    per-candidate measurement (tests); ``timer`` reaches the real
    measurement's clock.  Ties go to the earliest candidate, so a
    deterministic measure makes the search deterministic.

    When a usable :class:`tune.model.CostModel` is passed, the grid
    BEYOND the heuristic is reordered by predicted time and only the
    top-``top_k`` (default :func:`model_top_k` of the budget) are
    timed — the heuristic itself is always candidate #0, so a wrong
    model can waste predictions but never lose to v1's baseline.
    Predicted-vs-measured error is journaled as ``autotune.model_*``
    telemetry.  A model that raises, or one not ``usable``, falls back
    to the full log-distance-ordered budget (v1 behaviour, exactly)."""
    cands = candidates(family, shape, dtype)
    if not cands:
        return None
    space = len(cands)
    budget = max(1, int(trials)) if trials is not None else len(cands)
    preds = None
    ranked = False
    if model is not None and getattr(model, "usable", False):
        try:
            preds = [model.predict_config_ms(shape, dtype, c)
                     for c in cands]
        except Exception:
            preds = None
        if preds is not None:
            k = int(top_k) if top_k is not None else model_top_k(budget)
            k = max(1, min(k, budget))
            order = sorted(range(1, len(cands)),
                           key=lambda i: (preds[i],
                                          tuple(sorted(cands[i].items()))))
            keep = [0] + order
            pairs = [(cands[i], preds[i]) for i in keep[:k]]
            cands = [c for c, _ in pairs]
            preds = [p for _, p in pairs]
            ranked = True
    if not ranked:
        cands = cands[:budget]
    measure = measure or (lambda cfg: _measure_candidate(
        family, shape, dtype, cfg, calls=calls, warmup=warmup,
        timer=timer, interpret=interpret))
    results = []
    best = None
    for i, cfg in enumerate(cands):
        try:
            ms = float(measure(cfg))
        except Exception as e:     # a candidate that fails to compile
            results.append({"config": cfg, "error": repr(e)[:200]})
            continue
        r = {"config": cfg, "ms": round(ms, 6)}
        if ranked:
            r["pred_ms"] = round(float(preds[i]), 6)
        results.append(r)
        if best is None or ms < best[1]:
            best = (cfg, ms)
    if best is None:
        return None
    if ranked:
        _journal_model_error(family, shape, dtype, model, results)
    return {"config": dict(best[0]), "best_ms": best[1],
            "source": "searched",
            "trials": sum(1 for r in results if "ms" in r),
            "space": space, "interpret": bool(interpret),
            "ranked": ranked, "results": results}


def _journal_model_error(family, shape, dtype, model, results):
    """One ``autotune`` / ``model`` event per ranked search: how wrong
    the predictions were against what was actually measured — the
    honesty signal ``tools/parse_log.py --jsonl`` renders and the CV
    gate is calibrated against."""
    errs = [abs(r["pred_ms"] / r["ms"] - 1.0)
            for r in results if "ms" in r and "pred_ms" in r
            and r["ms"] > 0]
    if not errs:
        return
    try:
        from .. import telemetry
        telemetry.inc("autotune.model_rank")
        telemetry.event(
            "autotune", "model", family=family, shape=list(shape),
            dtype=str(dtype), n=len(errs),
            mean_err_pct=round(100.0 * sum(errs) / len(errs), 2),
            max_err_pct=round(100.0 * max(errs), 2),
            cv_error=getattr(model, "cv_error", None),
            n_samples=getattr(model, "n_samples", None))
    except Exception:
        pass


# ---------------------------------------------------------------------------
# XLA cost analysis (shared by bench._step_cost_analysis / cost_probe)
# ---------------------------------------------------------------------------

def compiled_cost(lowered):
    """Compile a lowered jit computation and return its XLA cost
    analysis as ``{"flops", "bytes_accessed"[, "temp_bytes"]}`` —
    the one place that knows about the list-wrapped cost dict and the
    optional memory analysis."""
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    out = {"flops": float(cost.get("flops", 0.0)),
           "bytes_accessed": float(cost.get("bytes accessed", 0.0))}
    try:
        out["temp_bytes"] = int(compiled.memory_analysis()
                                .temp_size_in_bytes)
    except Exception:
        pass
    return out
