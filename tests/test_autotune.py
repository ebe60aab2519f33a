"""Search-based Pallas autotuner tests (CPU-safe).

Covers the PR contract end to end: cost-table round-trip (write →
reload → dispatch hit), corrupt/stale-schema tolerance (heuristic
fallback, never a crash), deterministic offline search under a fake
measurer, the strict dispatch-time trial budget, and — the regression
guard — that DEFAULT dispatch (no table, no ``MXNET_AUTOTUNE``) is
bit-identical to the pre-autotuner heuristics for attention and the
layernorm block picker.
"""
import json
import os

import pytest

from mxnet_tpu import context, telemetry, tune
from mxnet_tpu.ops import pallas_attention as PA
from mxnet_tpu.ops import pallas_layernorm as LN
from mxnet_tpu.tune import search
from mxnet_tpu.tune.cost_table import CostTable, SCHEMA_VERSION


@pytest.fixture(autouse=True)
def _isolated_table(tmp_path, monkeypatch):
    """Every test gets its own table path and a reset singleton; the
    autotune env knobs start unset (default mode)."""
    monkeypatch.setenv("MXNET_AUTOTUNE_TABLE",
                       str(tmp_path / "cost_table.jsonl"))
    for var in ("MXNET_AUTOTUNE", "MXNET_AUTOTUNE_TRIALS",
                "MXNET_AUTOTUNE_CALLS", "MXNET_AUTOTUNE_INTERPRET"):
        monkeypatch.delenv(var, raising=False)
    tune._reset_for_tests()
    yield
    tune._reset_for_tests()


def _counter(name):
    return telemetry.counter(name)


# --- cost table ------------------------------------------------------------

def test_cost_table_roundtrip_dispatch_hit():
    """write → reload from disk → attention_dispatch serves the stored
    config with tuner_source=table (and counts the hit)."""
    t = tune.get_table()
    t.record("attention", (512, 512, 64), "bfloat16",
             {"block_q": 256, "block_k": 512}, best_ms=1.25,
             source="offline", trials=9)
    # fresh singleton: the entry must come back from DISK, not memory
    tune._reset_for_tests()
    hits = _counter("autotune.hit")
    plan = PA.attention_dispatch(512, 512, 64, "bfloat16", on_tpu=True)
    assert (plan["block_q"], plan["block_k"]) == (256, 512)
    assert plan["tuner_source"] == "table"
    assert plan["kernel"] == "short_seq"
    assert _counter("autotune.hit") == hits + 1
    # the stored record carries provenance for the census
    rec = tune.get_table().lookup("attention", (512, 512, 64), "bfloat16")
    assert rec["source"] == "offline" and rec["trials"] == 9
    assert rec["best_ms"] == pytest.approx(1.25)


def test_norm_pickers_consult_table():
    t = tune.get_table()
    # layernorm keys dtype-blind (fp32 VMEM working set): an entry
    # recorded from bf16 operands serves the picker's float32 lookup
    t.record("layernorm", (4096, 1024), "bfloat16", {"block_rows": 128})
    assert LN._pick_block_rows(1024, rows=4096) == 128
    # other shapes keep the heuristic
    assert LN._pick_block_rows(768, rows=4096) == \
        LN._pick_block_rows_heuristic(768)


def test_flash_bwd_threads_tuned_blocks(monkeypatch):
    """The production VJP must run the backward with the SAME tuned
    blocks the forward dispatched — the A/B acceptance leg times tuned
    fwd+bwd together, so a heuristic bwd would bench a config that
    never runs."""
    import jax.numpy as jnp
    import numpy as onp
    tune.get_table().record("attention", (384, 384, 64), "bfloat16",
                            {"block_q": 128, "block_k": 384})
    captured = {}

    def fake_bwd(q, k, v, out, lse, g, **kw):
        captured.update(kw)
        return q, k, v
    monkeypatch.setattr(PA, "pallas_flash_attention_bwd", fake_bwd)
    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    x = jnp.asarray(onp.zeros((1, 1, 384, 64), "float32"), jnp.bfloat16)
    lse = jnp.zeros((1, 1, 384), jnp.float32)
    res = (x, x, x, x, lse, None, None, None)
    PA._flash_bwd(False, None, res, x)
    assert captured["block_q"] == 128 and captured["block_k"] == 384


def test_corrupt_and_stale_entries_fall_back(tmp_path):
    """Garbage lines, stale schema versions and field-less configs are
    skipped (counted), never raised; valid records still serve."""
    path = os.environ["MXNET_AUTOTUNE_TABLE"]
    good = {"schema": SCHEMA_VERSION, "family": "attention",
            "shape": [512, 512, 64], "dtype": "bfloat16",
            "platform": tune.platform_id(),
            "config": {"block_q": 256, "block_k": 512}}
    with open(path, "w") as fh:
        fh.write("{ not json at all\n")
        fh.write(json.dumps(dict(good, schema=SCHEMA_VERSION + 1,
                                 shape=[128, 128, 64])) + "\n")
        fh.write(json.dumps(dict(good, shape=[256, 256, 64],
                                 config={"block_q": "x"})) + "\n")
        # float shape dims (an external serializer / hand edit): must
        # be SKIPPED, not raise TypeError out of canon_shape
        fh.write(json.dumps(dict(good, shape=[640.0, 640, 64])) + "\n")
        fh.write(json.dumps(good) + "\n")
    before = _counter("autotune.corrupt_entry")
    # corrupt keys -> heuristic, silently
    p128 = PA.attention_dispatch(128, 128, 64, "bfloat16", on_tpu=True)
    assert p128["tuner_source"] == "heuristic"
    assert (p128["block_q"], p128["block_k"]) == \
        PA.tune_attention_blocks(128, 128, 64, "bfloat16")
    p256 = PA.attention_dispatch(256, 256, 64, "bfloat16", on_tpu=True)
    assert p256["tuner_source"] == "heuristic"
    # the valid record on the same file still serves
    p512 = PA.attention_dispatch(512, 512, 64, "bfloat16", on_tpu=True)
    assert p512["tuner_source"] == "table" and p512["block_q"] == 256
    assert _counter("autotune.corrupt_entry") == before + 4


def test_invalid_table_config_falls_back():
    """A stored config that no longer satisfies the kernels' own VMEM
    predicate (e.g. a table baked before a budget change) is refused —
    heuristic + autotune.fallback, not a compile attempt."""
    tune.get_table().record("attention", (2048, 2048, 64), "bfloat16",
                            {"block_q": 4096, "block_k": 4096})
    fallbacks = _counter("autotune.fallback")
    plan = PA.attention_dispatch(2048, 2048, 64, "bfloat16", on_tpu=True)
    assert plan["tuner_source"] == "heuristic"
    assert (plan["block_q"], plan["block_k"]) == \
        PA.tune_attention_blocks(2048, 2048, 64, "bfloat16")
    assert _counter("autotune.fallback") == fallbacks + 1


def test_stale_entry_retuned_under_autotune(monkeypatch):
    """With MXNET_AUTOTUNE=1 an invalid table entry must fall THROUGH
    to the on-miss search (which overwrites the stale record) — not pin
    the shape to the heuristic forever."""
    tune.get_table().record("attention", (512, 512, 64), "bfloat16",
                            {"block_q": 4096, "block_k": 4096})
    monkeypatch.setattr(search, "_measure_candidate",
                        lambda f, s, d, cfg, **kw: float(cfg["block_q"]))
    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    monkeypatch.setenv("MXNET_AUTOTUNE", "1")
    plan = PA.attention_dispatch(512, 512, 64, "bfloat16", on_tpu=True)
    assert plan["tuner_source"] == "searched"
    rec = tune.get_table().lookup("attention", (512, 512, 64),
                                  "bfloat16")
    assert rec["config"]["block_q"] == plan["block_q"] != 4096


def test_invalid_entry_plus_failed_search_counts_one_fallback(monkeypatch):
    """One dispatch decision = one fallback event, even when an invalid
    entry's re-search then fails too."""
    tune.get_table().record("attention", (512, 512, 64), "bfloat16",
                            {"block_q": 4096, "block_k": 4096})

    def broken(*a, **kw):
        raise RuntimeError("no chip")
    monkeypatch.setattr(search, "_measure_candidate", broken)
    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    monkeypatch.setenv("MXNET_AUTOTUNE", "1")
    before = _counter("autotune.fallback")
    plan = PA.attention_dispatch(512, 512, 64, "bfloat16", on_tpu=True)
    assert plan["tuner_source"] == "heuristic"
    assert _counter("autotune.fallback") == before + 1


def test_interpret_records_refused_on_real_chip(monkeypatch):
    """Interpret-mode (smoke) timings are stamped into the record and
    never served on a real chip — there they read as a miss, so
    MXNET_AUTOTUNE can re-tune with real measurements."""
    tune.get_table().record("attention", (512, 512, 64), "bfloat16",
                            {"block_q": 256, "block_k": 512},
                            interpret=True)
    rec = tune.get_table().lookup("attention", (512, 512, 64),
                                  "bfloat16")
    assert rec is not None and rec["interpret"] is True  # CPU: servable
    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    assert tune.get_table().lookup("attention", (512, 512, 64),
                                   "bfloat16") is None


def test_platform_mismatch_is_a_miss():
    """A table baked on another chip generation must never serve."""
    tune.get_table().record("attention", (512, 512, 64), "bfloat16",
                            {"block_q": 256, "block_k": 512},
                            platform="tpu-v99")
    plan = PA.attention_dispatch(512, 512, 64, "bfloat16", on_tpu=True)
    assert plan["tuner_source"] == "heuristic"


# --- default mode: bit-identical to the pre-autotuner heuristics -----------

def test_default_dispatch_bit_identical_to_heuristic():
    """THE regression guard: with no table and MXNET_AUTOTUNE unset,
    every dispatch decision equals the pre-PR heuristic path exactly."""
    for s in (128, 384, 512, 1024, 2048, 4096, 8192):
        for d in (32, 64, 128):
            for dt in ("float32", "bfloat16"):
                plan = PA.attention_dispatch(s, s, d, dt, on_tpu=True)
                bq, bk = PA.tune_attention_blocks(s, s, d, dt)
                assert (plan["block_q"], plan["block_k"]) == (bq, bk), \
                    (s, d, dt, plan)
                assert plan["kernel"] == \
                    ("short_seq" if s <= bk else "streaming")
                assert plan["tuner_source"] == "heuristic"
    for C in (128, 768, 1024, 10 ** 6):
        assert LN._pick_block_rows(C, rows=4096) == \
            LN._pick_block_rows_heuristic(C)


def test_default_mode_never_searches(monkeypatch):
    """Default mode must measure NOTHING at trace time: the measurer is
    unreachable without the MXNET_AUTOTUNE opt-in."""
    def boom(*a, **k):
        raise AssertionError("measured in default mode")
    monkeypatch.setattr(search, "_measure_candidate", boom)
    plan = PA.attention_dispatch(640, 640, 64, "bfloat16", on_tpu=True)
    assert plan["tuner_source"] == "heuristic"
    LN._pick_block_rows(768, rows=512)


# --- search driver ---------------------------------------------------------

def test_candidates_prune_through_vmem_predicate():
    """Every enumerated candidate honours the kernels' own clamp —
    the search can never time (or emit) an over-budget config."""
    import jax.numpy as jnp
    for shape, dt in (((8192, 8192, 256), "float32"),
                      ((2048, 2048, 64), "bfloat16")):
        cands = search.candidates("attention", shape, dt)
        assert cands, shape
        assert cands[0] == search.heuristic_config("attention", shape, dt)
        Dp = shape[2] + (-shape[2]) % 64
        for c in cands:
            assert PA._fwd_vmem_bytes(c["block_q"], c["block_k"], Dp,
                                      jnp.dtype(dt).itemsize) \
                <= PA._VMEM_CLAMP, c
    for c in search.candidates("layernorm", (4096, 1024), "float32"):
        assert 3 * 4 * c["block_rows"] * 1024 <= LN._VMEM_BUDGET


def test_offline_search_deterministic_with_fake_timer():
    """Given a deterministic measurer, the search result is a pure
    function of the instance: same candidates, same winner (the argmin,
    earliest on ties), twice in a row."""
    def fake_ms(cfg):
        # prefers an interior point, deterministic in the config alone
        return abs(cfg["block_q"] - 256) + abs(cfg["block_k"] - 512) + 1.0
    a = search.search_config("attention", (512, 512, 64), "bfloat16",
                             trials=32, measure=fake_ms)
    b = search.search_config("attention", (512, 512, 64), "bfloat16",
                             trials=32, measure=fake_ms)
    assert a == b
    assert a["config"] == {"block_q": 256, "block_k": 512}
    assert a["best_ms"] == pytest.approx(1.0)
    timed = [r["config"] for r in a["results"]]
    assert timed == search.candidates("attention", (512, 512, 64),
                                      "bfloat16")[:32]


def test_search_survives_failing_candidates():
    """A candidate that raises (compile failure on some chip) is
    recorded and skipped — the search still returns the best of the
    rest."""
    def flaky(cfg):
        if cfg["block_q"] == 256:
            raise RuntimeError("mosaic says no")
        return cfg["block_q"]
    res = search.search_config("attention", (512, 512, 64), "bfloat16",
                               trials=8, measure=flaky)
    assert res["config"]["block_q"] != 256
    assert any("error" in r for r in res["results"])


def test_dispatch_search_honors_trial_budget(monkeypatch):
    """MXNET_AUTOTUNE=1 on-miss search: at most MXNET_AUTOTUNE_TRIALS
    candidates are measured, the winner is persisted, and the next
    dispatch is a table hit with no further measurement."""
    calls = []

    def fake_measure(family, shape, dtype, cfg, **kw):
        calls.append(dict(cfg))
        return float(cfg["block_q"])          # smallest block_q wins
    monkeypatch.setattr(search, "_measure_candidate", fake_measure)
    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    monkeypatch.setenv("MXNET_AUTOTUNE", "1")
    monkeypatch.setenv("MXNET_AUTOTUNE_TRIALS", "3")

    plan = PA.attention_dispatch(512, 512, 64, "bfloat16", on_tpu=True)
    assert plan["tuner_source"] == "searched"
    assert len(calls) == 3                     # the strict budget
    assert calls == search.candidates("attention", (512, 512, 64),
                                      "bfloat16")[:3]
    best_bq = min(c["block_q"] for c in calls)
    assert plan["block_q"] == best_bq
    # persisted: a fresh process (singleton reset) hits the table
    tune._reset_for_tests()
    plan2 = PA.attention_dispatch(512, 512, 64, "bfloat16", on_tpu=True)
    assert plan2["tuner_source"] == "table"
    assert plan2["block_q"] == best_bq
    assert len(calls) == 3                     # no re-measurement


def test_dispatch_search_needs_tpu_or_interpret_optin(monkeypatch):
    """MXNET_AUTOTUNE=1 on a CPU host must NOT try to time TPU kernels
    at dispatch (only the offline CLI's --interpret does that)."""
    def boom(*a, **k):
        raise AssertionError("searched on CPU without interpret opt-in")
    monkeypatch.setattr(search, "_measure_candidate", boom)
    monkeypatch.setenv("MXNET_AUTOTUNE", "1")
    plan = PA.attention_dispatch(512, 512, 64, "bfloat16", on_tpu=True)
    assert plan["tuner_source"] == "heuristic"


def test_table_blocks_default_and_field_order():
    assert tune.table_blocks("attention", (640, 640, 64), "bfloat16",
                             default=(1024, 2048)) == (1024, 2048)
    tune.get_table().record("attention", (640, 640, 64), "bfloat16",
                            {"block_q": 512, "block_k": 640})
    assert tune.table_blocks("attention", (640, 640, 64),
                             "bfloat16") == (512, 640)
    tune.get_table().record("layernorm", (0, 768), "float32",
                            {"block_rows": 64})
    # single-field family returns the bare int
    assert tune.table_blocks("layernorm", (0, 768), "float32") == 64


def test_norm_picker_census_is_once_per_decision():
    """One layernorm routing decision censuses ONCE even though the
    backward re-reads the blocks (quiet secondary lookup)."""
    before = _counter("autotune.miss")
    LN._pick_block_rows(768, rows=512)                 # fwd
    LN._pick_block_rows(768, rows=512, quiet=True)     # bwd
    assert _counter("autotune.miss") == before + 1


def test_failed_dispatch_search_is_memoized(monkeypatch):
    """An on-miss search whose every candidate fails must not re-run at
    retraces / sibling call sites — the failure is memoized in-process
    (it cannot be cached on disk)."""
    calls = []

    def broken(family, shape, dtype, cfg, **kw):
        calls.append(1)
        raise RuntimeError("no chip")
    monkeypatch.setattr(search, "_measure_candidate", broken)
    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    monkeypatch.setenv("MXNET_AUTOTUNE", "1")
    monkeypatch.setenv("MXNET_AUTOTUNE_TRIALS", "2")
    p1 = PA.attention_dispatch(512, 512, 64, "bfloat16", on_tpu=True)
    n = len(calls)
    assert p1["tuner_source"] == "heuristic" and n == 2
    p2 = PA.attention_dispatch(512, 512, 64, "bfloat16", on_tpu=True)
    assert p2["tuner_source"] == "heuristic"
    assert len(calls) == n                 # no second search


def test_record_merges_concurrent_writers(tmp_path):
    """Two CostTable instances on one file (two processes): the second
    writer's whole-file rewrite must keep the first writer's entries
    (merge-on-write, last writer wins per KEY not per file)."""
    path = str(tmp_path / "shared.jsonl")
    a = CostTable(path)
    b = CostTable(path)
    b.lookup("attention", (1, 1, 1), "bfloat16")   # b loads (empty file)
    a.record("attention", (512, 512, 64), "bfloat16",
             {"block_q": 256, "block_k": 512})
    b.record("attention", (2048, 2048, 64), "bfloat16",
             {"block_q": 512, "block_k": 1024})    # stale view of a's write
    fresh = CostTable(path)
    assert fresh.lookup("attention", (512, 512, 64),
                        "bfloat16") is not None, "first writer clobbered"
    assert fresh.lookup("attention", (2048, 2048, 64),
                        "bfloat16") is not None
    # disk wins for keys a process never wrote: b's stale startup view
    # of (512,...) must NOT revert a's re-tuned config when b records
    # an unrelated key
    a.record("attention", (512, 512, 64), "bfloat16",
             {"block_q": 512, "block_k": 512})       # a re-tunes X
    b.record("attention", (128, 128, 64), "bfloat16",
             {"block_q": 128, "block_k": 128})       # b writes Y
    final = CostTable(path)
    assert final.lookup("attention", (512, 512, 64),
                        "bfloat16")["config"]["block_q"] == 512, \
        "stale cache reverted a newer on-disk record"
    # an entry the operator DELETES from the file (the bench hard-fail
    # remedy) must not be resurrected by a process's stale cache
    kept = [ln for ln in open(path) if '"shape": [512, 512, 64]' not in ln]
    with open(path, "w") as fh:
        fh.writelines(kept)
    a.record("attention", (64, 64, 64), "bfloat16",
             {"block_q": 64, "block_k": 128})        # a's cache holds X
    assert CostTable(path).lookup("attention", (512, 512, 64),
                                  "bfloat16") is None, \
        "deleted entry resurrected by a stale cache"


def test_autotune_env_falsy_spellings(monkeypatch):
    for v in ("0", "false", "False", "OFF", "No", "", " off "):
        monkeypatch.setenv("MXNET_AUTOTUNE", v)
        assert not tune.autotune_enabled(), repr(v)
    for v in ("1", "true", "on"):
        monkeypatch.setenv("MXNET_AUTOTUNE", v)
        assert tune.autotune_enabled(), repr(v)


# --- offline CLI (interpret mode, tiny shape) ------------------------------

def test_offline_cli_searches_and_persists(capsys):
    """python -m mxnet_tpu.tune end to end on CPU via interpret mode:
    real Pallas measurements, winner persisted, --list round-trip."""
    from mxnet_tpu.tune.__main__ import main
    path = os.environ["MXNET_AUTOTUNE_TABLE"]
    rc = main(["--family", "layernorm", "--shape", "64:128",
               "--dtype", "float32", "--interpret", "--trials", "2",
               "--calls", "1"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["config"]["block_rows"] in (8, 16, 32, 64, 512)
    assert line["trials"] == 2 and line["best_ms"] > 0
    rc = main(["--list"])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip())
    assert rec["family"] == "layernorm" and rec["source"] == "offline"
    assert os.path.exists(path)
    # and the layernorm picker now serves it (same-process dispatch)
    tune._reset_for_tests()
    assert LN._pick_block_rows(128, rows=64) == \
        rec["config"]["block_rows"]


# --- telemetry census / parse_log round-trip -------------------------------

def test_parse_log_renders_autotune_census(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import parse_log

    tune.get_table().record("attention", (512, 512, 64), "bfloat16",
                            {"block_q": 256, "block_k": 512})
    PA.attention_dispatch(512, 512, 64, "bfloat16", on_tpu=True)   # hit
    PA.attention_dispatch(4096, 4096, 64, "bfloat16", on_tpu=True)  # miss
    path = str(tmp_path / "telemetry.jsonl")
    telemetry.export_jsonl(path)
    with open(path) as fh:
        agg = parse_log.parse_jsonl(fh)
    sources = [(e["family"], e["source"]) for e in agg["autotune"]]
    assert ("attention", "hit") in sources
    assert ("attention", "miss") in sources
    hit = next(e for e in agg["autotune"]
               if e["source"] == "hit" and e["shape"] == [512, 512, 64])
    assert hit["config"] == {"block_q": 256, "block_k": 512}
    text = parse_log.render_jsonl(agg)
    assert "autotune decisions" in text
    assert "512x512x64" in text and "block_q=256" in text
    assert "counter:autotune.hit" in text