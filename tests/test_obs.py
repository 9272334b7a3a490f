"""Observability contracts (repro.obs): quantile math, schemas, no-op cost.

Four guarantees under test:

  * **Histogram quantiles** -- the fixed-boundary estimator must track the
    numpy sample oracle to within one bucket width (it stores buckets, not
    samples; that bound is the whole design).
  * **Trace schema** -- a *real* traced 8-wave job must export Chrome
    ``trace_event`` JSON that passes ``validate_trace`` and whose named child
    spans cover >= 90% of the root span's wall time (the attribution
    acceptance bar).
  * **Counter parity** -- monolithic ``run_plan`` and ``WaveExecutor.run``
    must emit the same counter *names* with normalized types for every
    method; wave-only keys are exactly the documented ones.  (Values can
    differ legitimately: per-wave combining dedups less, apriori pruning
    weakens at tau=1.)
  * **Disabled == free** -- with tracing off, ``trace.span`` returns the
    shared null singleton and a full wave run performs zero
    ``jax.block_until_ready`` calls, creates no profiler annotation and
    allocates nothing in ``obs/trace.py``.
  * **One clock with the profiler** -- enabled, every span enters exactly one
    ``jax.profiler.TraceAnnotation`` of its name, so it lands in the
    profiler's ``.xplane.pb`` beside the device ops.
"""
import json

import numpy as np
import pytest

from repro.core import METHODS, NGramConfig, run_job
from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace
from repro.pipeline import WaveExecutor
from tests.test_compress import make_corpus


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends on the disabled singletons."""
    obs_trace.disable_tracing()
    obs_metrics.set_registry(None)
    yield
    obs_trace.disable_tracing()
    obs_metrics.set_registry(None)


# ------------------------------------------------------------ histograms

@pytest.mark.parametrize("dist", ["uniform", "lognormal", "bimodal"])
def test_histogram_quantiles_vs_numpy_oracle(dist):
    rng = np.random.default_rng(hash(dist) % 2**31)
    if dist == "uniform":
        xs = rng.uniform(0.0, 1.0, 5000)
    elif dist == "lognormal":
        xs = rng.lognormal(-7.0, 1.0, 5000)       # latency-shaped, ~1ms
    else:
        xs = np.concatenate([rng.uniform(1e-4, 2e-4, 2500),
                             rng.uniform(1e-2, 2e-2, 2500)])
    h = obs_metrics.Histogram("t")
    for x in xs:
        h.observe(x)
    b = np.asarray(h.boundaries)
    n = len(xs)
    for q in (0.5, 0.95, 0.99):
        est = h.quantile(q)
        # oracle bound: the order-statistic neighborhood of q (the empirical
        # CDF may jump across a mass gap, where every value in the gap is an
        # equally valid quantile), widened by the estimate's bucket width
        ref_lo = float(np.quantile(xs, max(q - 1.5 / n, 0.0)))
        ref_hi = float(np.quantile(xs, min(q + 1.5 / n, 1.0)))
        i = int(np.searchsorted(b, est))
        lo = b[i - 1] if i > 0 else float(xs.min())
        hi = b[i] if i < len(b) else float(xs.max())
        w = hi - lo
        assert ref_lo - w - 1e-12 <= est <= ref_hi + w + 1e-12, \
            f"{dist} q={q}: est={est} ref=[{ref_lo},{ref_hi}] width={w}"
    assert h.count == len(xs)
    assert h.min == pytest.approx(xs.min())
    assert h.max == pytest.approx(xs.max())
    assert h.mean == pytest.approx(xs.mean())


def test_histogram_edges():
    h = obs_metrics.Histogram("t", boundaries=[1.0, 2.0, 4.0])
    assert h.quantile(0.5) == 0.0                 # empty
    h.observe(3.0)
    assert h.quantile(0.0) <= 3.0 <= h.quantile(1.0) + 1e-12
    assert h.quantile(1.0) == pytest.approx(3.0)  # clamped to observed max
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        obs_metrics.Histogram("bad", boundaries=[2.0, 1.0])
    snap = h.snapshot()
    assert obs_report.validate_metrics(
        {"counters": {}, "gauges": {}, "histograms": {"t": snap}}) == []


# ------------------------------------------------------------ trace schema

def test_traced_eight_wave_run_schema_and_coverage(tmp_path):
    toks = make_corpus(4000, 60, "zipf", 0)
    cfg = NGramConfig(sigma=3, tau=3, vocab_size=60)
    wave = -(-len(toks) // 8)
    tracer = obs_trace.enable_tracing()
    try:
        stats = WaveExecutor(cfg, wave_tokens=wave).run(toks)
    finally:
        obs_trace.disable_tracing()
    assert stats.counters["waves"] == 8
    path = tmp_path / "trace.json"
    tracer.save(str(path))
    obj = json.loads(path.read_text())
    assert obs_report.validate_trace(obj) == []
    names = {e["name"] for e in obj["traceEvents"]}
    assert {"wave.run", "wave.submit", "wave.collect", "wave.fold",
            "wave.finalize"} <= names
    assert sum(e["name"] == "wave.submit" for e in obj["traceEvents"]) == 8
    # attribution bar: named child spans cover >= 90% of the root's wall time
    assert obs_trace.span_coverage(obj, "wave.run") >= 0.90


COLLECT_SPANS = ("wave.collect.wait", "wave.collect.d2h", "wave.collect.rows",
                 "wave.collect.sort")
FINALIZE_SPANS = ("merge.kway.concat", "merge.kway.sort", "merge.kway.fold",
                  "merge.pad", "stats.filter", "stats.unpack")


@pytest.fixture(scope="module")
def eight_wave_trace():
    """One traced 8-wave job: (exported trace, its stats)."""
    toks = make_corpus(40000, 60, "zipf", 10)
    cfg = NGramConfig(sigma=3, tau=3, vocab_size=60)
    wave = -(-len(toks) // 8)
    WaveExecutor(cfg, wave_tokens=wave).run(toks)     # compile untraced
    tracer = obs_trace.enable_tracing()
    try:
        stats = WaveExecutor(cfg, wave_tokens=wave).run(toks)
    finally:
        obs_trace.disable_tracing()
    return tracer.export(), stats


def _inside(inner, outer) -> bool:
    return (inner["tid"] == outer["tid"] and inner["ts"] >= outer["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


def test_device_finalize_block_spans_nest_in_finalize(monkeypatch):
    """The blocked device finalize: one ``merge.device.block`` span per
    counted block, inside ``merge.segments`` inside ``wave.finalize``;
    their ``rows`` add up to ``fold_rows`` and their ``kept`` to the job's
    output rows (the fold already dropped the rows under tau)."""
    from repro.index import merge as merge_mod
    monkeypatch.setattr(merge_mod, "DEVICE_BLOCK_ROWS", 256)
    toks = make_corpus(6000, 60, "zipf", 11)
    cfg = NGramConfig(sigma=3, tau=3, vocab_size=60)
    tracer = obs_trace.enable_tracing()
    try:
        stats = WaveExecutor(cfg, wave_tokens=-(-len(toks) // 4),
                             merge_route="device").run(toks)
    finally:
        obs_trace.disable_tracing()
    evs = tracer.export()["traceEvents"]
    blocks = [e for e in evs if e["name"] == "merge.device.block"]
    assert len(blocks) == stats.counters["finalize_blocks"] > 1
    for b in blocks:
        assert any(_inside(b, m) for m in evs if m["name"] == "merge.segments")
        assert any(_inside(b, f) for f in evs if f["name"] == "wave.finalize")
    assert sum(b["args"]["rows"] for b in blocks) == \
        stats.counters["fold_rows"]
    assert sum(b["args"]["kept"] for b in blocks) == len(stats)


def test_split_job_spans_and_counters(monkeypatch):
    """A head/tail split job (head width shrunk so it engages): one
    ``wave.heads`` span, one ``wave.tail`` span a wave carrying ``wave``,
    ``positions`` and ``rows``, whose sums are the job's
    ``tail_positions`` counter and the tail segment rows folded; the split's
    counters reach the metrics registry like every job counter."""
    from repro.pipeline import executor
    monkeypatch.setattr(executor, "SPLIT_HEAD_LANES", 2)
    rng = np.random.default_rng(3)
    quote = rng.integers(1, 200_000, 9).astype(np.int32)
    toks = np.concatenate(
        [np.concatenate([make_corpus(60, 50, "zipf", i), quote, [0]])
         for i in range(12)]).astype(np.int32)
    cfg = NGramConfig(sigma=7, tau=3, vocab_size=200_000)
    tracer = obs_trace.enable_tracing()
    try:
        stats = WaveExecutor(cfg, wave_tokens=-(-len(toks) // 3)).run(toks)
    finally:
        obs_trace.disable_tracing()
    evs = tracer.export()["traceEvents"]
    assert obs_report.validate_trace(tracer.export()) == []
    heads = [e for e in evs if e["name"] == "wave.heads"]
    tails = [e for e in evs if e["name"] == "wave.tail"]
    assert len(heads) == 1
    assert heads[0]["args"]["rows"] == stats.counters["head_dict_rows"] > 0
    assert sorted(e["args"]["wave"] for e in tails) == [0, 1, 2]
    assert sum(e["args"]["positions"] for e in tails) == \
        stats.counters["tail_positions"] > 0
    tail_folds = [e for e in evs if e["name"] == "wave.fold"
                  and e["args"].get("tail")]
    assert sum(e["args"]["rows"] for e in tails) == \
        sum(e["args"]["rows"] for e in tail_folds) > 0
    for t in tails:
        assert any(_inside(d, t) for d in evs
                   if d["name"] == "wave.collect.d2h")
    reg = obs_metrics.MetricsRegistry()
    reg.merge_job_counters(stats.counters)
    for k in ("head_dict_rows", "tail_positions", "tail_retries"):
        assert reg.counters["job." + k] == stats.counters[k]


def test_collect_and_finalize_spans_nest_and_join_by_wave(eight_wave_trace):
    obj, stats = eight_wave_trace
    evs = obj["traceEvents"]
    assert obs_report.validate_trace(obj) == []
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    assert set(COLLECT_SPANS + FINALIZE_SPANS + ("wave.feed.wait",)) \
        <= set(by_name)
    for name in COLLECT_SPANS:
        for e in by_name[name]:
            parent = [c for c in by_name["wave.collect"] if _inside(e, c)]
            assert len(parent) == 1, name
            assert e["args"]["wave"] == parent[0]["args"]["wave"]
    for name in FINALIZE_SPANS:
        for e in by_name[name]:
            assert any(_inside(e, f) for f in by_name["wave.finalize"]), name
    for name in FINALIZE_SPANS[:3]:
        for e in by_name[name]:
            assert any(_inside(e, m) for m in by_name["merge.segments"]), name
    # one wave's spans join across the feeder and fold threads by ``wave``
    submits = {e["args"]["wave"]: e for e in by_name["wave.submit"]}
    assert sorted(submits) == list(range(8))
    for name in ("wave.collect",) + COLLECT_SPANS + ("wave.feed.wait",):
        assert {e["args"]["wave"] for e in by_name[name]} == set(range(8))
    for c in by_name["wave.collect"]:
        sub = submits[c["args"]["wave"]]
        assert sub["tid"] != c["tid"] and sub["ts"] <= c["ts"]
    # the counter and the spans agree on the bytes brought to the host
    d2h = sum(e["args"]["bytes"] for e in by_name["wave.collect.d2h"])
    assert stats.counters["d2h_bytes"] == d2h > 0


def test_leaf_spans_cover_the_wave_run(eight_wave_trace):
    """Spans with no child on their thread, unioned over both threads,
    account for at least 80% of the job: little host time is unnamed."""
    obj, _ = eight_wave_trace
    evs = obj["traceEvents"]
    leaves = [e for e in evs
              if not any(o is not e and _inside(o, e) and o["dur"] < e["dur"]
                         for o in evs)]
    assert {"wave.collect.sort", "merge.kway.sort", "stats.unpack"} \
        <= {e["name"] for e in leaves}
    leaf_trace = {"traceEvents": leaves + [
        e for e in evs if e["name"] == "wave.run"]}
    assert obs_trace.span_coverage(leaf_trace, "wave.run") >= 0.80


def test_monolithic_trace_has_per_round_spans():
    toks = make_corpus(1500, 40, "zipf", 1)
    cfg = NGramConfig(sigma=3, tau=2, vocab_size=40)
    tracer = obs_trace.enable_tracing()
    try:
        run_job(toks, cfg)
    finally:
        obs_trace.disable_tracing()
    names = {e["name"] for e in tracer.export()["traceEvents"]}
    assert {"plan.run", "round.emit", "round.stages",
            "round.materialize"} <= names


def test_wave_program_keeps_its_module_name_and_names_its_stages():
    """The benchmark's readers find the wave program as the XLA module
    ``jit_wave_fn``; its stages carry their names into the op metadata."""
    import jax
    import jax.numpy as jnp
    from repro.pipeline.executor import _build_wave_program
    from repro.pipeline.plan import plan_for
    cfg = NGramConfig(sigma=3, tau=2, vocab_size=40)
    fn = _build_wave_program(cfg, plan_for(cfg))
    lowered = fn.lower(jax.ShapeDtypeStruct((258,), jnp.int32),
                       jnp.int32(256))
    assert lowered.as_text().startswith("module @jit_wave_fn")
    hlo = lowered.compile().as_text()
    assert "HloModule jit_wave_fn" in hlo
    for stage in ("emit", "sort", "reduce", "partition"):
        assert f"/{stage}/" in hlo, stage


# ------------------------------------------------------------ counter parity

@pytest.mark.parametrize("method", sorted(METHODS))
def test_counters_parity_monolithic_vs_wave(method):
    toks = make_corpus(2000, 50, "zipf", hash(method) % 2**31)
    cfg = NGramConfig(sigma=3, tau=3, vocab_size=50, method=method)
    mono = run_job(toks, cfg)
    wavy = WaveExecutor(cfg, wave_tokens=-(-len(toks) // 4)).run(toks)
    wave_only = {k for k, doc in obs_metrics.COUNTER_DOC.items()
                 if doc.endswith("(wave-only)")}
    assert wave_only == {"waves", "fold_rows", "finalize_blocks", "d2h_bytes",
                         "head_dict_rows", "tail_positions", "tail_retries"}
    assert set(wavy.counters) - wave_only == set(mono.counters)
    assert wave_only <= set(wavy.counters)
    # every emitted key is documented in the one canonical glossary
    for k in set(mono.counters) | set(wavy.counters):
        assert k in obs_metrics.COUNTER_DOC, f"undocumented counter {k!r}"
    # normalized types: float for ratio keys, int for counts -- on both paths
    for counters in (mono.counters, wavy.counters):
        for k, v in counters.items():
            want = float if k in obs_metrics.FLOAT_COUNTERS else int
            assert type(v) is want, f"{k}: {type(v).__name__}"


def test_merge_policy_sums_except_skew():
    dst = {"jobs": 2, "shuffle_skew": 1.5}
    obs_metrics.merge_counter_dicts(dst, {"jobs": 3, "shuffle_skew": 1.2,
                                          "retries": 1})
    assert dst == {"jobs": 5, "shuffle_skew": 1.5, "retries": 1}
    reg = obs_metrics.MetricsRegistry()
    reg.merge_job_counters({"jobs": 2, "shuffle_skew": 3.5})
    reg.merge_job_counters({"jobs": 1, "shuffle_skew": 2.0})
    assert reg.counters["job.jobs"] == 3
    assert reg.snapshot()["gauges"]["job.shuffle_skew"] == 3.5


# ------------------------------------------------------------ disabled == free

class _CountingAnnotation:
    """Stand-in for ``jax.profiler.TraceAnnotation`` that records its use."""
    made: list = []

    def __init__(self, name, **kw):
        self.name = name
        self.entered = self.exited = 0
        _CountingAnnotation.made.append(self)

    def __enter__(self):
        self.entered += 1
        return self

    def __exit__(self, *exc):
        self.exited += 1
        return False


@pytest.fixture
def counting_annotations(monkeypatch):
    import jax.profiler
    _CountingAnnotation.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    return _CountingAnnotation.made


def test_disabled_tracer_is_noop_and_sync_free(monkeypatch,
                                               counting_annotations):
    import jax
    assert obs_trace.span("anything") is obs_trace.NULL_SPAN
    sp = obs_trace.span("x")
    assert not sp and sp.set(a=1) is None

    calls = {"n": 0}
    real = jax.block_until_ready

    def counting(x):
        calls["n"] += 1
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    toks = make_corpus(1200, 40, "zipf", 2)
    cfg = NGramConfig(sigma=3, tau=2, vocab_size=40)
    WaveExecutor(cfg, wave_tokens=300).run(toks)
    assert calls["n"] == 0, \
        "disabled observability must not add block_until_ready syncs"
    assert counting_annotations == [], \
        "disabled observability must not create profiler annotations"


def test_disabled_tracer_allocates_nothing():
    import tracemalloc
    toks = make_corpus(1200, 40, "zipf", 2)
    cfg = NGramConfig(sigma=3, tau=2, vocab_size=40)
    WaveExecutor(cfg, wave_tokens=300).run(toks)      # compile first
    tracemalloc.start()
    try:
        WaveExecutor(cfg, wave_tokens=300).run(toks)
        for _ in range(1000):
            with obs_trace.span("x") as sp:
                if sp:
                    sp.set(a=1)
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    mine = snap.filter_traces(
        [tracemalloc.Filter(True, obs_trace.__file__)])
    assert mine.statistics("filename") == []


def test_enabled_span_enters_one_annotation(counting_annotations):
    toks = make_corpus(1200, 40, "zipf", 2)
    cfg = NGramConfig(sigma=3, tau=2, vocab_size=40)
    tracer = obs_trace.enable_tracing()
    try:
        WaveExecutor(cfg, wave_tokens=300).run(toks)
    finally:
        obs_trace.disable_tracing()
    names = sorted(e["name"] for e in tracer.events)
    assert len(names) > 8
    assert sorted(a.name for a in counting_annotations) == names
    assert all(a.entered == a.exited == 1 for a in counting_annotations)


def test_spans_sit_in_the_profiler_trace(tmp_path):
    """Under the JAX profiler every span is also a host event of the same
    name in the ``.xplane.pb``, at a fixed offset from its own clock."""
    import glob
    import jax
    toks = make_corpus(1200, 40, "zipf", 3)
    cfg = NGramConfig(sigma=3, tau=2, vocab_size=40)
    WaveExecutor(cfg, wave_tokens=300).run(toks)      # compile first
    tracer = obs_trace.enable_tracing()
    jax.profiler.start_trace(str(tmp_path))
    try:
        WaveExecutor(cfg, wave_tokens=300).run(toks)
    finally:
        jax.profiler.stop_trace()
        obs_trace.disable_tracing()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e["name"] for e in tracer.events}
    ann = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    ann.setdefault(ev.name, []).append(ev.start_ns)
    offsets = []
    for name in names:
        mine = sorted(e["ts"] * 1e3 + tracer._t_origin
                      for e in tracer.events if e["name"] == name)
        theirs = sorted(ann.get(name, []))
        assert len(theirs) == len(mine), name
        offsets += [a - m for a, m in zip(theirs, mine)]
    assert max(offsets) - min(offsets) < 50e6       # ns: one clock offset


def test_null_registry_instruments_are_noops():
    reg = obs_metrics.get_registry()
    assert not reg
    reg.counter("c").add(5)
    reg.gauge("g").set(2)
    reg.histogram("h").observe(0.1)
    reg.merge_job_counters({"jobs": 1})
    assert obs_metrics.get_registry().counter("c").value == 0


# ------------------------------------------------------------ metrics export

def test_registry_snapshot_roundtrips_through_validator(tmp_path):
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.set_registry(reg)
    toks = make_corpus(1500, 40, "zipf", 3)
    cfg = NGramConfig(sigma=3, tau=2, vocab_size=40)
    stats = WaveExecutor(cfg, wave_tokens=400).run(toks)
    reg.merge_job_counters(stats.counters)
    reg.histogram("lat").observe(0.002)
    snap = reg.snapshot()
    assert obs_report.validate_metrics(snap) == []
    path = tmp_path / "m.jsonl"
    env = obs_report.environment_metadata()
    import jax
    assert (env["backend"], env["device_kind"], env["device_count"]) == (
        jax.default_backend(), jax.devices()[0].device_kind,
        jax.device_count())
    obs_report.write_jsonl(str(path), [{"metrics": snap, "env": env}])
    assert obs_report.main(["--validate-metrics", str(path)]) == 0
    table = obs_report.summary_table(snap)
    assert "job.waves" in table and "lat" in table


def test_validators_reject_malformed():
    assert obs_report.validate_trace({}) != []
    assert obs_report.validate_trace(
        {"traceEvents": [{"name": "a", "ph": "B", "ts": 0, "dur": 1,
                          "pid": 0, "tid": 0}]}) != []
    bad = {"counters": {"c": "nope"}, "gauges": {}, "histograms": {}}
    assert obs_report.validate_metrics(bad) != []
    bad_h = {"counters": {}, "gauges": {}, "histograms": {
        "h": {"boundaries": [2.0, 1.0], "counts": [0, 0, 0], "count": 0,
              "sum": 0.0, "min": 0.0, "max": 0.0, "p50": 0.0, "p95": 0.0,
              "p99": 0.0}}}
    assert obs_report.validate_metrics(bad_h) != []


def test_lru_cache_surfaces_evictions_and_registry():
    from repro.launch.serve_ngrams import LRUQueryCache
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.set_registry(reg)
    c = LRUQueryCache(capacity=2)
    for i in range(4):
        c.get(("k", i), 0)
        c.put(("k", i), 0, i)
    assert c.evictions == 2 and c.misses == 4
    assert c.get(("k", 3), 0) == 3 and c.hits == 1
    c.publish_metrics()
    snap = reg.snapshot()
    assert snap["counters"]["cache.evictions"] == 2
    assert snap["counters"]["cache.hits"] == 1
    assert snap["gauges"]["cache.entries"] == 2
    c.publish_metrics()                            # lifetime mirror, not +=
    assert reg.snapshot()["counters"]["cache.evictions"] == 2
    assert obs_report.validate_metrics(snap) == []


def test_generational_compaction_stats_in_registry():
    from repro.index import GenerationalIndex
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.set_registry(reg)
    toks = make_corpus(1500, 40, "zipf", 4)
    cfg = NGramConfig(sigma=3, tau=1, vocab_size=40)
    gen = GenerationalIndex(sigma=3, vocab_size=40, size_ratio=2)
    for part in np.array_split(toks, 4):
        gen.ingest(run_job(part, cfg))
    assert gen.compaction_stats["ingests"] == 4
    snap = reg.snapshot()
    assert snap["counters"]["gen.ingests"] == 4
    assert snap["counters"]["gen.merges"] == gen.compaction_stats["merges"]
    assert snap["gauges"]["gen.segments"] == gen.n_segments
    assert snap["gauges"]["gen.rung0_rows"] == gen.levels[0].n_rows
    assert obs_report.validate_metrics(snap) == []


# ------------------------------------------------------------ compressed at rest

def test_compressed_at_rest_gauges():
    """Per-rung bytes-at-rest, the total, and the compressed-segment census
    land in the registry -- frozen rungs reporting persisted stream bytes,
    not the resident total with decoded query caches."""
    from repro.index import GenerationalIndex
    from repro.index.compress import CompressedNGramIndex
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.set_registry(reg)
    toks = make_corpus(3000, 40, "zipf", 5)
    cfg = NGramConfig(sigma=3, tau=1, vocab_size=40)
    gen = GenerationalIndex(sigma=3, vocab_size=40, size_ratio=2,
                            compress=True)
    for part in np.array_split(toks, 4):
        gen.ingest(run_job(part, cfg))
    assert gen.compaction_stats["merges"] >= 1
    segs = gen.segments          # materialize: merged rungs freeze compressed
    gen._publish_metrics()       # first publish after the lazy compression
    snap = reg.snapshot()
    want_total, want_comp = 0, 0
    for i, ix in enumerate(segs):
        b = getattr(ix, "nbytes_at_rest", None) or ix.nbytes
        assert snap["gauges"][f"gen.rung{i}_bytes_at_rest"] == b
        want_total += b
        want_comp += isinstance(ix, CompressedNGramIndex)
    assert snap["gauges"]["gen.bytes_at_rest"] == want_total
    assert snap["gauges"]["gen.compressed_segments"] == want_comp >= 1
    frozen = next(ix for ix in segs if isinstance(ix, CompressedNGramIndex))
    assert frozen.nbytes_at_rest < frozen.nbytes
    assert obs_report.validate_metrics(snap) == []


def test_streamed_decode_work_counters():
    """to_segment() and the compressed-native merge attribute their decode
    work to the registry: exactly the rows/block batches actually decoded."""
    from repro.index import build_compressed_index, merge_indexes
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.set_registry(reg)
    cfg = NGramConfig(sigma=3, tau=1, vocab_size=40)
    ca, cb = (build_compressed_index(
        run_job(make_corpus(1500, 40, "zipf", s), cfg), vocab_size=40)
        for s in (6, 7))
    nb = lambda ix: -(-ix.n_rows // ix.block_size)
    ca.to_segment()
    snap = reg.snapshot()
    assert snap["counters"]["compress.rows_decoded"] == ca.n_rows
    assert snap["counters"]["merge.blocks_decoded"] == nb(ca)
    merge_indexes([ca, cb], route="kway")
    snap = reg.snapshot()
    assert snap["counters"]["compress.rows_decoded"] == 2 * ca.n_rows + cb.n_rows
    assert snap["counters"]["merge.blocks_decoded"] == 2 * nb(ca) + nb(cb)
    assert obs_report.validate_metrics(snap) == []


def test_merge_span_records_layout_mix():
    """merge.segments spans carry the compressed/flat input mix."""
    from repro.index import build_compressed_index, merge_indexes
    cfg = NGramConfig(sigma=3, tau=1, vocab_size=40)
    cixs = [build_compressed_index(
        run_job(make_corpus(800, 40, "zipf", s), cfg), vocab_size=40)
        for s in (8, 9)]
    tracer = obs_trace.enable_tracing()
    try:
        merge_indexes(cixs, route="kway")
    finally:
        obs_trace.disable_tracing()
    evs = [e for e in tracer.export()["traceEvents"]
           if e["name"] == "merge.segments"]
    assert evs and evs[-1]["args"]["n_compressed"] == 2
    assert evs[-1]["args"]["n_flat"] == 0
