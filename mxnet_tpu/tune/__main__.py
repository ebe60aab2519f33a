"""Offline autotuning CLI.

    python -m mxnet_tpu.tune --family attention --shape 512:512:64 \
        --shape 8192:8192:64 --dtype bfloat16
    python -m mxnet_tpu.tune --family layernorm --shape 16384:1024
    python -m mxnet_tpu.tune --program
    python -m mxnet_tpu.tune --program --family prog_prefetch --shape 64
    python -m mxnet_tpu.tune --list

Searches each instance with the same driver the on-miss dispatch path
uses (wider default budget — offline time is cheap) and persists the
winners to the cost table, one JSON result line per instance.  Shapes
are colon-separated per family: attention ``seq_q:seq_k:head_dim``,
layernorm ``rows:channels`` (keyed dtype-blind — its VMEM working set
is fp32 whatever the operand dtype — so ``--dtype`` only picks the
measurement operands).  ``--interpret`` runs the kernels in Pallas
interpret mode so a table can be exercised end-to-end off-TPU
(functional, not representative — never ship interpret-mode timings as
a real chip's table).

Kernel searches are model-ranked when the learned cost model
(``tune.model``) is trained and within its CV gate — ``--no-model``
forces the v1 log-distance order.  Per-candidate timings are persisted
with the winner (they are the model's training data).

``--program`` switches to the whole-program schedule families
(``tune.program``): DevicePrefetchIter depth x decode workers, the
scan_steps window, ZeRO on/off, the serving bucket menu.  With no
``--family`` every program family is searched at its canonical
instance shape; shapes are colon-separated like the kernel families
(``prog_prefetch`` batch, ``prog_scan`` batch:hidden, ``prog_zero``
params:dp, ``prog_buckets`` max_batch).
"""
from __future__ import annotations

import argparse
import json
import sys

from . import get_table, platform_id, search
from .cost_table import FAMILY_FIELDS

_SHAPE_ARITY = {"attention": 3, "layernorm": 2,
                "prog_prefetch": 1, "prog_scan": 2, "prog_zero": 2,
                "prog_buckets": 1}


def _parse_shape(family, text):
    parts = tuple(int(x) for x in text.split(":"))
    if len(parts) != _SHAPE_ARITY[family]:
        raise SystemExit("--shape %s: %s expects %d ints"
                         % (text, family, _SHAPE_ARITY[family]))
    return parts


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m mxnet_tpu.tune")
    ap.add_argument("--family", choices=sorted(FAMILY_FIELDS),
                    default=None)
    ap.add_argument("--program", action="store_true",
                    help="search whole-program schedule knobs "
                         "(tune.program families) instead of kernel "
                         "blocks")
    ap.add_argument("--no-model", action="store_true",
                    help="disable learned-cost-model candidate ranking")
    ap.add_argument("--shape", action="append", default=[],
                    help="instance shape, colon-separated (repeatable)")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--trials", type=int, default=32,
                    help="max candidates timed per instance (offline "
                         "default is wide; dispatch-time uses "
                         "MXNET_AUTOTUNE_TRIALS)")
    ap.add_argument("--calls", type=int, default=search.DEFAULT_CALLS)
    ap.add_argument("--interpret", action="store_true",
                    help="Pallas interpret mode (off-TPU smoke runs)")
    ap.add_argument("--dry-run", action="store_true",
                    help="search but do not write the table")
    ap.add_argument("--table", default=None,
                    help="table path override (else MXNET_AUTOTUNE_TABLE "
                         "or the repo default)")
    ap.add_argument("--list", action="store_true",
                    help="print the table's entries and exit")
    args = ap.parse_args(argv)

    table = get_table()
    if args.table:
        from .cost_table import CostTable
        table = CostTable(args.table)
    if args.list:
        for rec in table.entries():
            print(json.dumps(rec))
        return 0
    if args.program:
        return _run_program(args, table)
    family = args.family or "attention"
    if family.startswith("prog_"):
        ap.error("program families need --program")
    if not args.shape:
        ap.error("at least one --shape is required (or --list/--program)")

    model = None
    if not args.no_model:
        from . import model as _model
        try:
            model = _model.get_model(family, table=table)
        except Exception:
            model = None
    rc = 0
    for text in args.shape:
        shape = _parse_shape(family, text)
        res = search.search_config(family, shape, args.dtype,
                                   trials=args.trials, calls=args.calls,
                                   interpret=args.interpret, model=model)
        line = {"family": family, "shape": list(shape),
                "dtype": args.dtype, "platform": platform_id(),
                "table": table.path}
        if res is None:
            line["error"] = "no candidate could be timed"
            rc = 1
        else:
            line.update(config=res["config"],
                        best_ms=round(res["best_ms"], 6),
                        trials=res["trials"], space=res["space"],
                        ranked=res.get("ranked", False),
                        results=res["results"])
            if family == "attention":
                line["kernel"] = search.attention_variant(
                    shape[1], res["config"]["block_k"])
            if not args.dry_run:
                # interpret provenance is stamped into the record:
                # lookup refuses interpret-timed configs on a real chip
                table.record(family, shape, args.dtype,
                             res["config"], best_ms=res["best_ms"],
                             source="offline", trials=res["trials"],
                             interpret=args.interpret,
                             results=res["results"])
        print(json.dumps(line), flush=True)
    return rc


def _run_program(args, table):
    """--program leg: measured schedule search per program family, one
    JSON line each, persisted through the same store."""
    from . import program as prog

    families = [args.family] if args.family else \
        list(prog.PROGRAM_FAMILIES)
    for f in families:
        if f not in prog.PROGRAM_FAMILIES:
            raise SystemExit("--program with --family %s: choose one of "
                             "%s" % (f, ", ".join(prog.PROGRAM_FAMILIES)))
    if args.shape and not args.family:
        raise SystemExit("--program --shape needs an explicit --family "
                         "(shapes are family-specific)")
    shapes = [_parse_shape(families[0], t) for t in args.shape] \
        if args.shape else [None]
    rc = 0
    for family in families:
        for shape in shapes:
            if shape is None:
                shape = prog.default_shape(family)
            res = prog.run_program_search(family, shape,
                                          calls=args.calls,
                                          record=False)
            if res is not None and not args.dry_run:
                table.record(family, shape, "float32", res["config"],
                             best_ms=res["best_ms"], source="searched",
                             trials=res["trials"],
                             results=res["results"])
            line = {"family": family, "shape": list(shape),
                    "platform": platform_id(), "table": table.path}
            if res is None:
                line["error"] = "no candidate could be timed"
                rc = 1
            else:
                line.update(config=res["config"],
                            best_ms=round(res["best_ms"], 6),
                            trials=res["trials"], space=res["space"],
                            strategy=res.get("strategy"))
                if family == "prog_buckets":
                    line["menu"] = prog.menu_from_config(res["config"])
            print(json.dumps(line), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
