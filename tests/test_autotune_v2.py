"""Autotuner v2 tests: learned cost model + whole-program schedule
search (CPU-safe, virtual 8-device mesh).

Covers the PR contract: deterministic seeded fits with built-in CV,
the hard ``usable`` fallback (empty/corrupt training data degrades to
v1's log-distance ordering, bit-exactly), interpret-sample exclusion on
real chips, model-ranked dispatch search timing strictly fewer
candidates than the v1 budget while never losing to the heuristic, the
miss -> ranked search -> persist round trip in interpret mode, the
lookup-only program-schedule families and their consumers
(``shard_optimizer="auto"`` measured vs heuristic, DevicePrefetchIter
depth, serving bucket menus under the HBM budget), and the
``tools/parse_log.py --jsonl`` v2 census round trip.
"""
import json
import os

import numpy as onp
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import context, telemetry, tune
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu import parallel
from mxnet_tpu.tune import search
from mxnet_tpu.tune import model as M
from mxnet_tpu.tune import program as prog
from mxnet_tpu.tune import cost_table as ct


@pytest.fixture(autouse=True)
def _isolated_table(tmp_path, monkeypatch):
    """Own table path + reset singletons; autotune env starts unset."""
    monkeypatch.setenv("MXNET_AUTOTUNE_TABLE",
                       str(tmp_path / "cost_table.jsonl"))
    for var in ("MXNET_AUTOTUNE", "MXNET_AUTOTUNE_TRIALS",
                "MXNET_AUTOTUNE_CALLS", "MXNET_AUTOTUNE_INTERPRET",
                "MXNET_AUTOTUNE_MODEL", "MXNET_AUTOTUNE_MODEL_CV",
                "MXNET_AUTOTUNE_MODEL_TOPK", "MXNET_AUTOTUNE_SPANS",
                "MXNET_SERVE_HBM_BUDGET"):
        monkeypatch.delenv(var, raising=False)
    tune._reset_for_tests()
    yield
    tune._reset_for_tests()


@pytest.fixture
def mesh8():
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
    m = parallel.device_mesh((8,), ("dp",))
    old = parallel.get_mesh()
    parallel.set_mesh(m)
    yield m
    parallel.set_mesh(old)


_SHAPE = (512, 512, 64)


def _smooth_ms(cfg):
    """Multiplicative ground truth: log(ms) is linear in the log2
    features, so the ridge fit on log(ms) is near-exact and the CV
    gate passes with margin."""
    return cfg["block_q"] * cfg["block_k"] / 2.0 ** 17 + 0.25


def _attention_samples(shape=_SHAPE, dtype="bfloat16"):
    return [(M.featurize("attention", shape, dtype, cfg),
             _smooth_ms(cfg))
            for cfg in search.candidates("attention", shape, dtype)]


# --- CostModel -------------------------------------------------------------

def test_fit_deterministic_and_serializable():
    samples = _attention_samples()
    assert len(samples) >= M.MIN_SAMPLES
    a = M.CostModel("attention").fit(samples, seed=0)
    b = M.CostModel("attention").fit(samples, seed=0)
    assert a.trained and a.usable
    assert a.weights == b.weights
    assert a.cv_error == b.cv_error
    # serialization round trip predicts identically
    c = M.CostModel.from_dict(a.to_dict())
    cfg = {"block_q": 256, "block_k": 512}
    assert c.predict_config_ms(_SHAPE, "bfloat16", cfg) == \
        pytest.approx(a.predict_config_ms(_SHAPE, "bfloat16", cfg))
    with pytest.raises(ValueError):
        M.CostModel.from_dict({"schema": 999})


def test_under_min_samples_is_untrained_and_unusable():
    m = M.CostModel("attention").fit(_attention_samples()[:M.MIN_SAMPLES - 1])
    assert not m.trained and not m.usable
    with pytest.raises(RuntimeError):
        m.predict_ms([0.0])


def test_cv_gate_refuses_noisy_model(monkeypatch):
    """A model whose CV error exceeds MXNET_AUTOTUNE_MODEL_CV is not
    usable even though it trained."""
    rng = onp.random.RandomState(7)
    noisy = [(f, ms * float(rng.uniform(0.05, 20.0)))
             for f, ms in _attention_samples()]
    m = M.CostModel("attention").fit(noisy)
    assert m.trained
    monkeypatch.setenv("MXNET_AUTOTUNE_MODEL_CV", "0.0001")
    assert not m.usable


def test_get_model_empty_table_returns_none_and_counts_fallback(
        monkeypatch):
    assert M.get_model("attention") is None
    # the dispatch-side acquisition journals the degradation to v1
    monkeypatch.setenv("MXNET_AUTOTUNE_INTERPRET", "1")
    monkeypatch.setenv("MXNET_AUTOTUNE_TRIALS", "1")
    monkeypatch.setenv("MXNET_AUTOTUNE_CALLS", "1")
    before = telemetry.counter("autotune.model_fallback")
    res = tune._dispatch_search("layernorm", (64, 256), "float32")
    assert res is not None and not res["ranked"]
    assert telemetry.counter("autotune.model_fallback") == before + 1
    snap = telemetry.snapshot(events=64)
    assert any(e.get("name") == "model_fallback"
               and e.get("reason") == "untrained_or_cv"
               for e in snap["events"])


def test_training_samples_skip_corrupt_entries():
    t = tune.get_table()
    good = [{"config": {"block_q": 128 * (i + 1), "block_k": 512},
             "ms": 1.0 + i} for i in range(4)]
    bad = [{"config": {"block_q": 128}, "ms": 2.0},          # field missing
           {"config": None, "ms": 1.0},                       # no config
           {"config": {"block_q": 128, "block_k": 512}, "ms": -1.0},
           "not-a-dict"]
    t.record("attention", _SHAPE, "bfloat16",
             {"block_q": 128, "block_k": 512}, best_ms=1.0,
             results=good + bad)
    samples = M.training_samples(t, "attention")
    assert len(samples) == len(good)
    # unknown family contributes nothing rather than raising
    assert M.training_samples(t, "nosuch") == []


def test_interpret_samples_excluded_on_real_chip(monkeypatch):
    t = tune.get_table()
    t.record("attention", _SHAPE, "bfloat16",
             {"block_q": 128, "block_k": 512}, best_ms=1.0,
             interpret=True,
             results=[{"config": {"block_q": 128, "block_k": 512},
                       "ms": 1.0}])
    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    assert M.training_samples(t, "attention") == []
    assert len(M.training_samples(t, "attention",
                                  include_interpret=True)) == 1
    monkeypatch.setattr(context, "on_tpu", lambda *a: False)
    assert len(M.training_samples(t, "attention")) == 1


def test_get_model_retrains_when_table_grows():
    t = tune.get_table()
    cands = search.candidates("attention", _SHAPE, "bfloat16")
    t.record("attention", _SHAPE, "bfloat16", cands[0],
             best_ms=_smooth_ms(cands[0]),
             results=[{"config": c, "ms": _smooth_ms(c)} for c in cands])
    m1 = M.get_model("attention", table=t)
    assert m1 is not None and m1.usable
    assert M.get_model("attention", table=t) is m1     # cached
    t.record("attention", (1024, 1024, 64), "bfloat16", cands[0],
             best_ms=2.0)
    m2 = M.get_model("attention", table=t)
    assert m2 is not m1                                # generation moved


def test_model_kill_switch(monkeypatch):
    monkeypatch.setenv("MXNET_AUTOTUNE_MODEL", "0")
    assert not M.model_enabled()
    assert M.get_model("attention") is None


# --- model-ranked search ---------------------------------------------------

def test_ranked_search_times_strictly_fewer_than_v1_budget():
    """THE acceptance gate: with a usable model the search measures
    strictly fewer candidates than the v1 budget, keeps the heuristic
    as candidate #0, and the winner never loses to it."""
    model = M.CostModel("attention").fit(_attention_samples())
    assert model.usable
    space = len(search.candidates("attention", _SHAPE, "bfloat16"))
    budget = space                      # v1 would measure the full grid
    v1 = search.search_config("attention", _SHAPE, "bfloat16",
                              trials=budget, measure=_smooth_ms)
    assert v1["trials"] == budget and not v1["ranked"]
    before = telemetry.counter("autotune.model_rank")
    v2 = search.search_config("attention", _SHAPE, "bfloat16",
                              trials=budget, measure=_smooth_ms,
                              model=model)
    assert v2["ranked"]
    assert v2["trials"] < budget
    # heuristic is always candidate #0...
    heur = search.heuristic_config("attention", _SHAPE, "bfloat16")
    assert v2["results"][0]["config"] == heur
    # ...so the ranked winner can never lose to v1's baseline
    assert v2["best_ms"] <= _smooth_ms(heur)
    assert v2["best_ms"] == v1["best_ms"]     # found the same optimum
    assert all("pred_ms" in r for r in v2["results"] if "ms" in r)
    assert telemetry.counter("autotune.model_rank") == before + 1
    snap = telemetry.snapshot(events=256)
    ev = [e for e in snap["events"]
          if e.get("kind") == "autotune" and e.get("name") == "model"]
    assert ev and ev[-1]["n"] == v2["trials"]
    assert ev[-1]["mean_err_pct"] < 20.0      # near-exact ground truth


def test_unusable_model_is_bit_identical_to_v1():
    untrained = M.CostModel("attention")
    v1 = search.search_config("attention", _SHAPE, "bfloat16",
                              trials=6, measure=_smooth_ms)
    v2 = search.search_config("attention", _SHAPE, "bfloat16",
                              trials=6, measure=_smooth_ms,
                              model=untrained)
    assert v1 == v2


def test_raising_model_falls_back_to_v1():
    class Hostile(M.CostModel):
        usable = True

        def predict_config_ms(self, *a):
            raise RuntimeError("boom")
    v1 = search.search_config("attention", _SHAPE, "bfloat16",
                              trials=6, measure=_smooth_ms)
    v2 = search.search_config("attention", _SHAPE, "bfloat16",
                              trials=6, measure=_smooth_ms,
                              model=Hostile("attention"))
    assert v1 == v2


def test_topk_env_override(monkeypatch):
    monkeypatch.setenv("MXNET_AUTOTUNE_MODEL_TOPK", "1")
    model = M.CostModel("attention").fit(_attention_samples())
    res = search.search_config("attention", _SHAPE, "bfloat16",
                               trials=16, measure=_smooth_ms,
                               model=model)
    # k=1 keeps only the heuristic — still a valid (v1-baseline) result
    assert res["trials"] == 1
    assert res["config"] == search.heuristic_config(
        "attention", _SHAPE, "bfloat16")


def test_miss_ranked_search_persists_roundtrip_interpret(monkeypatch):
    """MXNET_AUTOTUNE=1 in interpret mode: a miss trains the model from
    the table, runs a RANKED search over fewer candidates than the
    budget, persists winner + per-candidate results, and the next
    dispatch is a pure table hit."""
    t = tune.get_table()
    n_seed = 0
    for shape_seed in ((128, 512), (256, 1024)):
        cands = search.candidates("layernorm", shape_seed, "float32")
        t.record("layernorm", shape_seed, "float32", cands[0],
                 best_ms=1.0, interpret=True,
                 results=[{"config": c,
                           "ms": 0.05 * c["block_rows"]}
                          for c in cands])
        n_seed += len(cands)
    assert n_seed >= M.MIN_SAMPLES
    monkeypatch.setenv("MXNET_AUTOTUNE", "1")
    monkeypatch.setenv("MXNET_AUTOTUNE_INTERPRET", "1")
    monkeypatch.setenv("MXNET_AUTOTUNE_TRIALS", "4")
    monkeypatch.setenv("MXNET_AUTOTUNE_CALLS", "1")
    tune._reset_for_tests()
    ranks = telemetry.counter("autotune.model_rank")
    miss_shape = (64, 256)
    cfg = tune.table_config("layernorm", miss_shape, "float32")
    assert cfg is not None and cfg["source"] == "searched"
    assert telemetry.counter("autotune.model_rank") == ranks + 1
    rec = tune.get_table().lookup("layernorm", miss_shape, "float32")
    assert rec is not None and rec["interpret"]
    assert rec["source"] == "searched"
    timed = [r for r in rec["results"] if "ms" in r]
    assert 0 < len(timed) < 4          # ranked: fewer than the budget
    snap = telemetry.snapshot(events=256)
    ev = [e for e in snap["events"] if e.get("kind") == "autotune"
          and e.get("name") == "search"
          and e.get("family") == "layernorm"]
    assert ev and ev[-1]["ranked"] is True and ev[-1]["interpret"]
    # and the persisted winner now serves as a plain hit
    hits = telemetry.counter("autotune.hit")
    again = tune.table_config("layernorm", miss_shape, "float32")
    assert again["source"] == "table"
    assert {k: again[k] for k in ("block_rows",)} == \
        {k: cfg[k] for k in ("block_rows",)}
    assert telemetry.counter("autotune.hit") == hits + 1


# --- whole-program schedule search ----------------------------------------

def test_program_config_is_lookup_only():
    miss = telemetry.counter("autotune.program_miss")
    searches = telemetry.counter("autotune.program_search")
    assert prog.program_config("prog_prefetch", (64,)) is None
    assert telemetry.counter("autotune.program_miss") == miss + 1
    assert telemetry.counter("autotune.program_search") == searches
    with pytest.raises(ValueError):
        prog.program_config("attention", (64,))


def test_program_knobs_roundtrip_and_default():
    assert prog.program_knobs("prog_prefetch", (64,),
                              default=(2, 1)) == (2, 1)
    tune.get_table().record("prog_prefetch", (64,), "float32",
                            {"depth": 4, "workers": 2}, best_ms=0.5,
                            source="searched")
    hits = telemetry.counter("autotune.program_hit")
    assert prog.program_knobs("prog_prefetch", (64,)) == (4, 2)
    assert telemetry.counter("autotune.program_hit") == hits + 1
    # single-field family returns the scalar; the package-level alias
    # goes through the same store
    tune.get_table().record("prog_scan", (32, 256), "float32",
                            {"k": 4}, best_ms=0.5, source="searched")
    assert tune.program_knobs("prog_scan", (32, 256), default=1) == 4


def test_invalid_program_entry_falls_back():
    tune.get_table().record("prog_prefetch", (64,), "float32",
                            {"depth": 999, "workers": 1}, best_ms=0.5)
    fb = telemetry.counter("autotune.program_fallback")
    assert prog.program_config("prog_prefetch", (64,)) is None
    assert telemetry.counter("autotune.program_fallback") == fb + 1


def test_search_program_deterministic_with_fake_measure():
    def fake(cfg, calls):
        return abs(cfg["k"] - 4) + 1.0
    a = prog.search_program("prog_scan", (32, 256), measure=fake)
    b = prog.search_program("prog_scan", (32, 256), measure=fake)
    assert a == b
    assert a["config"] == {"k": 4} and a["strategy"] in ("sh", "cd")
    # multi-axis grid goes through coordinate descent and converges in
    # fewer measurements than the full grid
    def fake2(cfg, calls):
        return abs(cfg["depth"] - 4) + abs(cfg["workers"] - 2) + 1.0
    r = prog.search_program("prog_prefetch", (64,), measure=fake2)
    assert r["config"] == {"depth": 4, "workers": 2}
    assert r["strategy"] == "cd"
    assert r["trials"] < r["space"] * 2


def test_bucket_menu_round_trip_and_hbm_validation():
    assert prog.menu_from_config({"max_bucket": 8, "levels": 3}) == \
        [2, 4, 8]
    assert prog.config_from_menu([2, 4, 8]) == \
        {"max_bucket": 8, "levels": 3}
    # over-budget menus drop the largest bucket first, never empty out:
    # in+out of buckets {2,4} at feat=1024 fp32 is 2*(2+4)*1024*4 bytes
    menu = prog.validate_menu([2, 4, 8], (1024,), "float32",
                              budget=2 * 6 * 1024 * 4)
    assert menu == [2, 4]
    tiny = prog.validate_menu([64], (1024 * 1024,), "float32", budget=1)
    assert tiny == [64]                          # never empties

    from mxnet_tpu.serve.buckets import default_bucket_menu
    menu, src = default_bucket_menu(max_batch=8, feature_shape=(16,))
    assert src == "heuristic" and menu[-1] == 8
    tune.get_table().record("prog_buckets", (8,), "float32",
                            {"max_bucket": 8, "levels": 2}, best_ms=1.0,
                            source="searched")
    menu, src = default_bucket_menu(max_batch=8, feature_shape=(16,))
    assert src == "table" and menu == [4, 8]
    # a non-power-of-two cap canonicalizes onto the same table key
    menu, src = default_bucket_menu(max_batch=6, feature_shape=(16,))
    assert src == "table" and menu == [4, 8]


def test_prefetch_iter_depth_from_table():
    from mxnet_tpu.io import DataBatch, DataDesc, DataIter
    from mxnet_tpu.io.device_prefetch import DevicePrefetchIter

    class TinyIter(DataIter):
        def __init__(self):
            super().__init__(64)
            self.i = 0

        @property
        def provide_data(self):
            return [DataDesc("data", (64, 4))]

        @property
        def provide_label(self):
            return [DataDesc("softmax_label", (64,))]

        def reset(self):
            self.i = 0

        def next(self):
            if self.i >= 2:
                raise StopIteration
            self.i += 1
            return DataBatch(
                [mx.nd.zeros((64, 4), dtype="uint8")],
                [mx.nd.zeros((64,))], pad=0)

    def probe(depth):
        feed = DevicePrefetchIter(TinyIter(), dtype="float32",
                                  depth=depth)
        try:
            return feed._depth, feed.tuner_source
        finally:
            feed.close()

    assert probe(None) == (2, "heuristic")
    tune.get_table().record("prog_prefetch", (64,), "float32",
                            {"depth": 4, "workers": 1}, best_ms=0.5,
                            source="searched")
    assert probe(None) == (4, "table")
    # explicit depth is untouched (bit-identical v1 behaviour)
    assert probe(3) == (3, "explicit")


# --- shard_optimizer="auto" ------------------------------------------------

def _auto_step(mesh):
    onp.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(7, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(mx.nd.array(onp.zeros((8, 9), "float32")))
    L = gloss.SoftmaxCrossEntropyLoss()
    return parallel.DataParallelStep(
        net, lambda o, l: L(o, l), mx.optimizer.SGD(learning_rate=0.1),
        mesh=mesh, shard_optimizer="auto")


def _last_zero_event():
    snap = telemetry.snapshot(events=256)
    evs = [e for e in snap["events"] if e.get("kind") == "zero"
           and e.get("name") == "auto_decision"]
    return evs[-1] if evs else None


def test_auto_shard_heuristic_path(mesh8):
    st = _auto_step(mesh8)
    assert st._shard_n == 8
    ev = _last_zero_event()
    assert ev and ev["path"] == "heuristic" and ev["shard"] is True
    assert ev["tuner_source"] == "heuristic" and ev["dp"] == 8
    assert ev["params"] > 0


def test_auto_shard_measured_veto(mesh8):
    """A measured prog_zero entry saying shard=0 overrides the
    heuristic — and the decision is journaled as measured."""
    pcount = 9 * 7 + 7 + 7 * 4 + 4          # the probe net's weights
    key = (prog.canon_param_count(pcount), 8)
    tune.get_table().record("prog_zero", key, "float32", {"shard": 0},
                            best_ms=1.0, source="searched")
    st = _auto_step(mesh8)
    assert st._shard_n == 0
    ev = _last_zero_event()
    assert ev and ev["path"] == "measured" and ev["shard"] is False
    assert ev["tuner_source"] == "table"
    # and the flipped table entry turns sharding back on
    tune.get_table().record("prog_zero", key, "float32", {"shard": 1},
                            best_ms=1.0, source="searched")
    st = _auto_step(mesh8)
    assert st._shard_n == 8
    ev = _last_zero_event()
    assert ev["path"] == "measured" and ev["shard"] is True


# --- parse_log --jsonl v2 census ------------------------------------------

def test_parse_log_renders_v2_census(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import parse_log

    # model-ranked search -> autotune/model error event + counter
    model = M.CostModel("attention").fit(_attention_samples())
    search.search_config("attention", _SHAPE, "bfloat16", trials=16,
                         measure=_smooth_ms, model=model)
    # program decisions: one miss, one hit
    prog.program_config("prog_scan", (32, 256))
    tune.get_table().record("prog_scan", (32, 256), "float32",
                            {"k": 4}, best_ms=0.5, source="searched")
    prog.program_config("prog_scan", (32, 256))
    # the consumer-side events the census also rows up (emitted by
    # DataParallelStep / InferenceServer in-process; synthesized here
    # so the round trip stays mesh-free)
    telemetry.event("zero", "auto_decision", path="measured",
                    shard=False, params=4096, dp=8, tuner_source="table")
    telemetry.event("serve", "bucket_menu", model="m", buckets=[4, 8],
                    tuner_source="table")

    path = str(tmp_path / "telemetry.jsonl")
    telemetry.export_jsonl(path)
    with open(path) as fh:
        agg = parse_log.parse_jsonl(fh)
    assert agg["model"]["errors"], "ranked search must journal an error row"
    err = agg["model"]["errors"][-1]
    assert err["family"] == "attention" and err["n"] > 0
    events = [(e["event"], e["source"]) for e in agg["program"]]
    assert ("program/miss", "heuristic") in events
    assert ("program/hit", "table") in events
    assert ("zero/auto_decision", "table") in events
    assert ("serve/bucket_menu", "table") in events

    text = parse_log.render_jsonl(agg)
    assert "autotune cost model (predicted vs measured" in text
    assert "model_rank=" in text
    assert "program schedule decisions:" in text
    assert "program/hit" in text and "k=4" in text
    assert "zero/auto_decision" in text and "shard=False" in text
    # tsv mode renders the same censuses without markdown pipes
    tsv = parse_log.render_jsonl(agg, fmt="tsv")
    assert "program/hit\tprog_scan" in tsv


def test_parse_log_model_fallback_tally(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import parse_log

    lines = [json.dumps({"kind": "autotune", "name": "model_fallback",
                         "reason": "untrained_or_cv"})] * 3
    agg = parse_log.parse_jsonl(lines)
    assert agg["model"]["fallbacks"] == {"untrained_or_cv": 3}
    assert "fallback[untrained_or_cv]=3" in parse_log.render_jsonl(agg)
