"""Persistent cost table for the Pallas autotuner (TVM-style cost
records, arxiv 1802.04799).

One JSONL file, one record per tuned instance, keyed exactly like the
jit cache keys a config will be compiled under:

    (family, canonical shape tuple, canonical dtype, platform id,
     schema version)

so a table baked on one chip generation never leaks configs onto
another.  The store is deliberately boring:

* **atomic writes** — the whole file is rewritten to a temp sibling and
  ``os.replace``d, so a killed process can at worst lose the newest
  record, never corrupt the file;
* **corrupt-entry tolerance** — an unparsable line, a stale
  ``schema``, or a record missing its family's config fields is
  SKIPPED (counted on ``autotune.corrupt_entry``), never raised: a bad
  table degrades to the heuristic, it cannot take training down;
* **process-level cache** — the file is read once; lookups afterwards
  are one dict probe, cheap enough to sit on the trace-time dispatch
  path.
"""
from __future__ import annotations

import json
import operator
import os
import threading
from typing import Dict, Optional, Tuple

from .. import context as _context

SCHEMA_VERSION = 1

# family -> ordered config fields (the tuple order table_blocks returns)
FAMILY_FIELDS = {
    "attention": ("block_q", "block_k"),
    "layernorm": ("block_rows",),
    # program-level schedule knobs (tune.program) share the store and
    # its discipline: same schema, same atomicity, same provenance
    "prog_prefetch": ("depth", "workers"),
    "prog_scan": ("k",),
    "prog_zero": ("shard",),
    "prog_buckets": ("max_bucket", "levels"),
    # gradient-wire compression mode (0 off / 1 int8 / 2 fp8) — the
    # ONE program family keyed on the real operand dtype (see
    # _KEY_DTYPE): the wire narrowing is a dtype decision
    "prog_compress": ("mode",),
}

# kernel families a table MISS may trigger a measured kernel search for
# (tune.search.candidates only knows these; prog_* misses must resolve
# through tune.program's own search, never a kernel grid)
KERNEL_FAMILIES = ("attention", "layernorm")

# the layernorm kernels hold their working values as fp32 in VMEM
# regardless of the operand dtype, so their block choice is dtype-blind:
# the table key pins dtype="float32" for them (an entry baked from bf16
# operands serves the f32 run and vice versa — and the offline CLI's
# default --dtype cannot strand an entry under an unreachable key)
_KEY_DTYPE = {"layernorm": "float32",
              # program knobs are dtype-blind by construction: their
              # shapes are workload descriptors (batch, params, dp...),
              # not array operands — EXCEPT prog_compress, whose knob
              # is precisely a wire-dtype choice and therefore keys on
              # the real gradient dtype
              "prog_prefetch": "float32", "prog_scan": "float32",
              "prog_zero": "float32", "prog_buckets": "float32"}

_PLATFORM = {"id": None}
_platform_lock = threading.Lock()


def canon_dtype(dtype, family=None) -> str:
    """Canonical dtype string for a table key ('bfloat16', 'float32',
    ...); dtype-blind families pin to their fixed key dtype."""
    fixed = _KEY_DTYPE.get(family)
    if fixed is not None:
        return fixed
    try:
        import jax.numpy as jnp
        return str(jnp.dtype(dtype))
    except Exception:
        return str(dtype)


def canon_shape(shape) -> Tuple[int, ...]:
    # operator.index, not int(): shape dims are static Python ints by
    # contract — index() refuses arrays instead of syncing them
    return tuple(operator.index(x) for x in shape)


def platform_id() -> str:
    """Chip identity the table is keyed on: the device kind as jax
    reports it ('TPU v5 lite' -> 'tpu-v5-lite', 'cpu' on the host
    backend).  A config measured on one chip generation must never be
    served on another — so a device jax cannot read raises; it does not
    become a key of its own."""
    with _platform_lock:
        if _PLATFORM["id"] is None:
            import jax
            dev = jax.devices()[0]
            kind = dev.device_kind or dev.platform
            _PLATFORM["id"] = str(kind).strip().lower().replace(" ", "-")
        return _PLATFORM["id"]


def default_table_path() -> str:
    """``MXNET_AUTOTUNE_TABLE`` or ``<repo>/.autotune/cost_table.jsonl``
    (next to the jit executables' ``.jax_cache`` — same lifecycle: both
    are warm-start artifacts a deployment ships alongside the code)."""
    env = os.environ.get("MXNET_AUTOTUNE_TABLE")
    if env:
        return env
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".autotune", "cost_table.jsonl")


def baked_table_path() -> Optional[str]:
    """The shipped read-only warm-start table, or None.

    ``MXNET_AUTOTUNE_BAKED`` points at one explicitly; otherwise the
    repo ships per-platform tables at ``.autotune/baked/<platform>.jsonl``
    (committed, unlike the writable runtime table) — but ONLY when the
    runtime table is the default one: a test or operator that repoints
    ``MXNET_AUTOTUNE_TABLE`` has asked for a hermetic store, and baked
    entries leaking into it would un-hermeticize every lookup."""
    env = os.environ.get("MXNET_AUTOTUNE_BAKED")
    if env:
        return env
    if os.environ.get("MXNET_AUTOTUNE_TABLE"):
        return None
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(root, ".autotune", "baked",
                        "%s.jsonl" % platform_id())
    return path if os.path.exists(path) else None


class _file_lock:
    """Advisory sidecar flock (``<table>.lock``) closing the cross-
    process read-merge-replace window in :meth:`CostTable.record`.
    Best-effort: on platforms without fcntl the merge still runs, it is
    just advisory-free (the pre-lock behaviour)."""

    def __init__(self, path):
        self._path = path + ".lock"
        self._fh = None

    def __enter__(self):
        try:
            import fcntl
            d = os.path.dirname(self._path)
            if d:
                os.makedirs(d, exist_ok=True)
            self._fh = open(self._path, "a")
            fcntl.flock(self._fh.fileno(), fcntl.LOCK_EX)
        except (ImportError, OSError):
            self._fh = None
        return self

    def __exit__(self, *a):
        if self._fh is not None:
            try:
                self._fh.close()     # releases the flock
            except OSError:
                pass


def _valid_record(rec) -> bool:
    if not isinstance(rec, dict) or rec.get("schema") != SCHEMA_VERSION:
        return False
    fields = FAMILY_FIELDS.get(rec.get("family"))
    if fields is None:
        return False
    cfg = rec.get("config")
    if not isinstance(cfg, dict) or \
            not all(isinstance(cfg.get(f), int)
                    and not isinstance(cfg.get(f), bool)
                    for f in fields):
        return False
    shape = rec.get("shape")
    # shape elements must be true ints — a float (an external
    # serializer, a hand edit) would make canon_shape raise out of a
    # load that promises tolerance
    return isinstance(shape, list) and \
        all(isinstance(x, int) and not isinstance(x, bool)
            for x in shape) and \
        isinstance(rec.get("dtype"), str) and \
        isinstance(rec.get("platform"), str)


def _read_records(path):
    """All valid (key, record) pairs from a JSONL table file plus the
    count of skipped (corrupt/stale/invalid) lines.  THE one
    read-parse-validate path — load and merge both use it.  Never
    raises: an unreadable file reads as empty."""
    out, corrupt = [], 0
    try:
        with open(path) as fh:
            lines = fh.readlines()
    except (OSError, IOError):
        return out, corrupt
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            rec = None
        if not _valid_record(rec):
            corrupt += 1
            continue
        key = (rec["family"], canon_shape(rec["shape"]),
               rec["dtype"], rec["platform"])
        out.append((key, rec))
    return out, corrupt


class CostTable:
    """In-memory view of one on-disk JSONL cost table."""

    def __init__(self, path: Optional[str] = None,
                 baked: Optional[str] = None):
        self.path = path or default_table_path()
        # read-only warm-start layer: baked records load first, the
        # writable file's records override per key, and record() only
        # ever rewrites the writable file
        self.baked = baked
        self._lock = threading.Lock()
        self._entries: Dict[tuple, dict] = {}
        self._loaded = False
        self.corrupt = 0
        # bumped on every record(); model caches key off it
        self.generation = 0

    def _key(self, family, shape, dtype, platform):
        return (family, canon_shape(shape), canon_dtype(dtype, family),
                platform or platform_id())

    def _load_locked(self):
        if self._loaded:
            return
        self._loaded = True
        corrupt = 0
        if self.baked:
            recs, c = _read_records(self.baked)
            for key, rec in recs:
                self._entries[key] = dict(rec, baked=True)
            corrupt += c
        recs, c = _read_records(self.path)
        for key, rec in recs:
            self._entries[key] = rec
        corrupt += c
        self.corrupt += corrupt
        if corrupt:
            from .. import telemetry
            telemetry.inc("autotune.corrupt_entry", corrupt)

    def lookup(self, family, shape, dtype, platform=None) -> Optional[dict]:
        """The stored record (dict) for an instance, or None.  Never
        raises: a missing/corrupt table is a miss.  Interpret-stamped
        records (functional smoke timings) are refused on a real chip —
        a miss there lets MXNET_AUTOTUNE re-tune with real
        measurements instead of serving non-representative configs."""
        with self._lock:
            self._load_locked()
            rec = self._entries.get(self._key(family, shape, dtype,
                                              platform))
            if rec is not None and rec.get("interpret") and \
                    _context.on_tpu():
                return None
            return dict(rec) if rec else None

    def record(self, family, shape, dtype, config, best_ms=None,
               source="offline", trials=None, platform=None,
               interpret=False, results=None):
        """Insert/overwrite one entry and persist the whole table
        atomically (temp sibling + os.replace).  ``interpret`` stamps
        configs chosen from Pallas interpret-mode timings — provenance
        the lookup uses to refuse serving them on a real chip.
        ``results`` optionally keeps the search's per-candidate timings
        (``[{"config": {...}, "ms": float}, ...]``, capped at 64) —
        they are the learned cost model's training set, so a search's
        losers are worth persisting too."""
        fields = FAMILY_FIELDS[family]
        cfg = {f: int(config[f]) for f in fields}
        rec = {"schema": SCHEMA_VERSION, "family": family,
               "shape": list(canon_shape(shape)),
               "dtype": canon_dtype(dtype, family),
               "platform": platform or platform_id(),
               "config": cfg, "source": source}
        if best_ms is not None:
            rec["best_ms"] = round(float(best_ms), 6)
        if trials is not None:
            rec["trials"] = int(trials)
        if interpret:
            rec["interpret"] = True
        if results:
            kept = []
            for r in results:
                if not isinstance(r, dict) or "ms" not in r:
                    continue   # errored candidates teach nothing
                try:
                    kept.append({"config": {f: int(r["config"][f])
                                            for f in fields},
                                 "ms": round(float(r["ms"]), 6)})
                except (KeyError, TypeError, ValueError):
                    continue
            if kept:
                rec["results"] = kept[:64]
        with self._lock:
            self._load_locked()
            # rebuild-from-disk under a sidecar flock: the file is the
            # source of truth for every key except the one being
            # recorded — a concurrent writer's entries survive, a
            # re-tune by another process wins, and an entry an operator
            # DELETED from the file stays deleted (a stale cache must
            # not resurrect it).  Net effect: last-writer-wins per KEY,
            # with the read-rebuild-replace window closed against
            # concurrent writers by the advisory file lock.
            with _file_lock(self.path):
                self._rebuild_from_disk_locked()
                self._entries[self._key(family, shape, dtype,
                                        platform)] = rec
                self._write_locked()
            self.generation += 1
        return rec

    def _rebuild_from_disk_locked(self):
        """Replace the in-memory view with the file's current valid
        records before a rewrite (the caller re-asserts the one key it
        is recording): every on-disk record postdates this process's
        cached view, and a key ABSENT from disk was deleted on purpose
        — neither may lose to a stale cache.  The read-only baked layer
        is re-applied underneath (``baked=True``-marked, so the rewrite
        below never copies it into the writable file)."""
        entries = {}
        if self.baked:
            for key, r in _read_records(self.baked)[0]:
                entries[key] = dict(r, baked=True)
        entries.update(dict(_read_records(self.path)[0]))
        self._entries = entries

    def entries(self):
        with self._lock:
            self._load_locked()
            return [dict(r) for _, r in sorted(self._entries.items(),
                                               key=lambda kv: repr(kv[0]))]

    def _write_locked(self):
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        # fsutil owns the tmp + os.replace discipline (and its commit
        # window consults the artifact_write_crash chaos mode)
        from ..fsutil import atomic_write_path
        with atomic_write_path(self.path) as tmp:
            with open(tmp, "w") as fh:
                for _, rec in sorted(self._entries.items(),
                                     key=lambda kv: repr(kv[0])):
                    if rec.get("baked"):
                        continue   # the shipped layer is read-only
                    fh.write(json.dumps(rec) + "\n")


def _reset_platform_cache():
    """Test hook: forget the cached platform id."""
    with _platform_lock:
        _PLATFORM["id"] = None
