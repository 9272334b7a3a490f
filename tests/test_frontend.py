"""Serving-frontend suite: batcher, admission, coalescing, HTTP round trip.

The layers are tested bottom-up with deterministic drivers (recording
executors, manual flush mode, injected clocks), then the whole stack --
HTTP/SSE transport -> admission -> continuous batcher -> service -> index --
is driven over localhost and checked **bit-identical** against direct
``StreamingNGramService`` calls (the oracle the acceptance criteria names).
"""
from __future__ import annotations

import http.client
import json
import time
from concurrent.futures import Future
from types import SimpleNamespace

import numpy as np
import pytest

from repro.serve.admission import (ADMIT, QUOTA, SHED, AdmissionController,
                                   TokenBucket)
from repro.serve.batcher import ContinuousBatcher, Request, select_bucket
from repro.serve.service import StreamingNGramService

SIGMA, VOCAB = 3, 30


# --------------------------------------------------------------------------- #
# deterministic plumbing (no jax)
# --------------------------------------------------------------------------- #

class RecordingExecutor:
    """Answers lookups as f(gram) so tests can check per-slot routing."""

    def __init__(self):
        self.batches = []          # (kind, k, grams, lengths) per flush
        self.collected = 0

    def submit(self, kind, k, grams, lengths):
        self.batches.append((kind, k, grams.copy(), lengths.copy()))
        return kind, k, grams.copy(), lengths.copy()

    def collect(self, rec):
        kind, k, g, ln = rec
        self.collected += 1
        if kind == "lookup":
            return (g[:, 0].astype(np.uint32) * 100
                    + ln.astype(np.uint32))
        rows = np.zeros((g.shape[0], 2 + 2 * k), np.uint32)
        rows[:, 0] = g[:, 0]
        return rows


def req(term: int, *, length: int = 1, kind: str = "lookup", k: int = 8,
        priority: int = 0) -> Request:
    gram = np.zeros((SIGMA,), np.int32)
    gram[0] = term
    return Request(kind, gram, length, k=k, priority=priority)


def stub_service(generation: int = 1):
    """The minimal service surface QueryFrontend needs (key fns + config)."""
    return SimpleNamespace(
        cfg=SimpleNamespace(sigma=SIGMA, vocab_size=VOCAB),
        gen=SimpleNamespace(generation=generation),
        lookup_key=StreamingNGramService.lookup_key,
        continuation_key=StreamingNGramService.continuation_key)


# ------------------------------------------------------------ bucket policy

def test_select_bucket_deterministic():
    buckets = (16, 64, 256)
    assert select_bucket(1, buckets) == 16
    assert select_bucket(16, buckets) == 16
    assert select_bucket(17, buckets) == 64
    assert select_bucket(65, buckets) == 256
    assert select_bucket(10_000, buckets) == 256   # the cap
    with pytest.raises(ValueError):
        select_bucket(0, buckets)


def test_flush_pads_to_bucket_and_zero_fills():
    ex = RecordingExecutor()
    b = ContinuousBatcher(ex, buckets=(4, 8), deadline_s=10.0, autostart=False)
    reqs = [req(t + 1) for t in range(3)]
    for r in reqs:
        b.enqueue(r)
    batch = b.flush_once(force=True)
    b.collect_inflight()
    assert [r.seq for r in batch] == [0, 1, 2]
    kind, _, g, ln = ex.batches[0]
    assert kind == "lookup" and g.shape == (4, SIGMA)    # 3 live -> bucket 4
    np.testing.assert_array_equal(g[:3, 0], [1, 2, 3])
    np.testing.assert_array_equal(g[3], 0)               # pad slot is zeros
    assert ln[3] == 0
    assert [r.future.result(0) for r in reqs] == [101, 201, 301]
    assert b.stats()["padded_slots"] == 1


def test_full_bucket_caps_flush_size():
    ex = RecordingExecutor()
    b = ContinuousBatcher(ex, buckets=(2, 4), deadline_s=10.0, autostart=False)
    for t in range(6):
        b.enqueue(req(t + 1))
    assert b.flush_once() is not None      # 6 queued >= cap 4: due immediately
    assert ex.batches[0][2].shape[0] == 4
    assert b.depth == 2


# -------------------------------------------------------- deadline semantics

def test_deadline_flush_without_busy_wait():
    """A partial bucket flushes at the deadline off a condition-variable wait:
    wall time reaches the deadline while the loop wakes O(1) times, and the
    stats prove no poll loop spun."""
    ex = RecordingExecutor()
    b = ContinuousBatcher(ex, buckets=(4, 8), deadline_s=0.05)
    try:
        t0 = time.perf_counter()
        reqs = [req(t + 1) for t in range(3)]
        for r in reqs:
            b.enqueue(r)
        vals = [r.future.result(timeout=5.0) for r in reqs]
        elapsed = time.perf_counter() - t0
        assert vals == [101, 201, 301]
        assert 0.02 <= elapsed <= 2.0        # flushed by deadline, not instantly
        st = b.stats()
        assert st["batches"] == 1 and st["requests"] == 3
        assert st["wait_cycles"] <= 10        # cond.wait(timeout), not a spin
    finally:
        b.stop()


def test_stop_drains_everything():
    ex = RecordingExecutor()
    b = ContinuousBatcher(ex, buckets=(4,), deadline_s=60.0)
    reqs = [req(t + 1) for t in range(3)]
    for r in reqs:
        b.enqueue(r)
    b.stop()                                 # deadline far away: stop flushes
    assert all(r.future.done() for r in reqs)
    with pytest.raises(RuntimeError):
        b.enqueue(req(9))


# ------------------------------------------------------------ priority order

def test_priority_ordering_under_contention():
    ex = RecordingExecutor()
    b = ContinuousBatcher(ex, buckets=(8,), deadline_s=10.0, autostart=False)
    low = [req(t + 1, priority=1) for t in range(3)]
    for r in low:
        b.enqueue(r)
    high = req(7, priority=0)
    b.enqueue(high)                          # arrives last, flushes first
    first = b.flush_once(force=True)
    second = b.flush_once(force=True)
    b.collect_inflight()
    assert first == [high]
    assert second == low
    assert ex.batches[0][2][0, 0] == 7
    np.testing.assert_array_equal(ex.batches[1][2][:3, 0], [1, 2, 3])


def test_lanes_split_by_kind_and_k():
    ex = RecordingExecutor()
    b = ContinuousBatcher(ex, buckets=(8,), deadline_s=10.0, autostart=False)
    b.enqueue(req(1))
    b.enqueue(req(2, kind="topk", k=4))
    b.enqueue(req(3))
    first = b.flush_once(force=True)         # oldest head wins: lookup lane
    second = b.flush_once(force=True)
    b.collect_inflight()
    assert [r.kind for r in first] == ["lookup", "lookup"]
    assert [r.seq for r in first] == [0, 2]
    assert [r.kind for r in second] == ["topk"]
    assert ex.batches[1][1] == 4             # k rides the lane


# -------------------------------------------------- cancelled never padded in

def test_cancelled_request_never_enters_device_batch():
    ex = RecordingExecutor()
    b = ContinuousBatcher(ex, buckets=(4, 8), deadline_s=10.0, autostart=False)
    reqs = [req(t + 1) for t in range(5)]
    for r in reqs:
        b.enqueue(r)
    assert reqs[1].cancel() and reqs[4].cancel()
    batch = b.flush_once(force=True)
    b.collect_inflight()
    assert [r.seq for r in batch] == [0, 2, 3]
    _, _, g, _ = ex.batches[0]
    assert g.shape[0] == 4                   # bucket chosen AFTER the filter
    np.testing.assert_array_equal(g[:, 0], [1, 3, 4, 0])
    assert reqs[1].future.cancelled() and reqs[4].future.cancelled()
    assert b.stats()["cancelled_dropped"] == 2
    assert b.depth == 0


def test_cancel_refused_with_followers_and_after_delivery():
    r = req(1)
    rider = Future()
    assert r.attach(rider)
    assert not r.cancel()                    # a follower still needs the row
    r.deliver(np.uint32(7))
    assert rider.result(0) == 7
    assert not r.cancel()                    # sealed
    assert not r.attach(rider)               # late duplicate must re-submit


# ------------------------------------------------------------------ admission

def test_token_bucket_exhaustion_and_recovery():
    t = [0.0]
    bucket = TokenBucket(rate=2.0, burst=4.0, clock=lambda: t[0])
    assert all(bucket.try_take() for _ in range(4))
    assert not bucket.try_take()             # burst drained
    t[0] += 1.0                              # +2 tokens
    assert bucket.try_take() and bucket.try_take()
    assert not bucket.try_take()
    t[0] += 100.0                            # refill clamps at burst
    assert sum(bucket.try_take() for _ in range(10)) == 4


def test_admission_priority_shedding_tiers():
    adm = AdmissionController(queue_budget=4, hard_limit=8)
    lo, hi = adm.level("batch"), adm.level("interactive")
    assert adm.admit(tenant="t", level=lo, queue_depth=3) == ADMIT
    assert adm.admit(tenant="t", level=lo, queue_depth=4) == SHED
    assert adm.admit(tenant="t", level=hi, queue_depth=4) == ADMIT
    assert adm.admit(tenant="t", level=hi, queue_depth=8) == SHED
    with pytest.raises(KeyError):
        adm.level("vip")


def test_admission_quota_is_per_tenant_and_recovers():
    t = [0.0]
    adm = AdmissionController(queue_budget=64, quota_rate=1.0, quota_burst=2.0,
                              clock=lambda: t[0])
    assert adm.admit(tenant="a", level=0, queue_depth=0) == ADMIT
    assert adm.admit(tenant="a", level=0, queue_depth=0) == ADMIT
    assert adm.admit(tenant="a", level=0, queue_depth=0) == QUOTA
    assert adm.admit(tenant="b", level=0, queue_depth=0) == ADMIT  # own bucket
    t[0] += 1.0
    assert adm.admit(tenant="a", level=0, queue_depth=0) == ADMIT  # recovered
    assert adm.admit(tenant="a", level=0, queue_depth=0) == QUOTA


# ---------------------------------------------------------------- frontend

def make_frontend(**admission_kw):
    from repro.serve.frontend import QueryFrontend
    return QueryFrontend(stub_service(), executor=RecordingExecutor(),
                         admission=AdmissionController(**admission_kw),
                         deadline_s=10.0, autostart=False)


def test_frontend_shed_and_quota_tickets():
    from repro.obs import metrics as obs_metrics
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.set_registry(reg)
    try:
        fe = make_frontend(queue_budget=0, hard_limit=1,
                           quota_rate=1.0, quota_burst=1.0)
        t_batch = fe.submit("lookup", [5], 1, priority="batch")
        assert t_batch.status == "shed" and not t_batch.admitted
        t_hi = fe.submit("lookup", [5], 1, priority="interactive")
        assert t_hi.status == "admitted"     # level 0 survives the soft budget
        # depth now 1 >= hard_limit: even interactive sheds
        assert fe.submit("lookup", [6], 1).status == "shed"
        # shed/quota'd requests never reached the batcher queue
        assert fe.batcher.depth == 1
        fe.batcher.stop()
        assert reg.counter("frontend.shed").value == 2
    finally:
        obs_metrics.set_registry(None)


def test_frontend_quota_rejection_counter():
    from repro.obs import metrics as obs_metrics
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.set_registry(reg)
    try:
        fe = make_frontend(queue_budget=64, quota_rate=0.001, quota_burst=1.0)
        assert fe.submit("lookup", [1], 1, tenant="t0").status == "admitted"
        assert fe.submit("lookup", [2], 1, tenant="t0").status == "quota"
        assert fe.submit("lookup", [2], 1, tenant="t1").status == "admitted"
        fe.batcher.stop()
        assert reg.counter("frontend.quota_rejected").value == 1
    finally:
        obs_metrics.set_registry(None)


def test_duplicate_coalescing_bit_identical_payloads():
    from repro.obs import metrics as obs_metrics
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.set_registry(reg)
    try:
        fe = make_frontend(queue_budget=64)
        a = fe.submit("lookup", [7, 8], 2)
        b = fe.submit("lookup", [7, 8], 2)       # identical, in flight
        c = fe.submit("lookup", [7, 9], 2)       # different gram
        assert (a.status, b.status, c.status) == \
            ("admitted", "coalesced", "admitted")
        fe.batcher.flush_once(force=True)
        fe.batcher.collect_inflight()
        pa, pb = a.future.result(0), b.future.result(0)
        assert pa == pb and pa.tobytes() == pb.tobytes()
        # the executor saw ONE slot for the duplicate pair (2 live, not 3)
        _, _, g, _ = fe.batcher.executor.batches[0]
        assert g.shape[0] == 16
        np.testing.assert_array_equal(g[:3, 0], [7, 7, 0])
        fe.batcher.stop()
        assert reg.counter("frontend.coalesced").value == 1
    finally:
        obs_metrics.set_registry(None)


def test_coalescing_key_includes_generation():
    fe = make_frontend(queue_budget=64)
    a = fe.submit("lookup", [7], 1)
    fe.service.gen.generation += 1               # ingest swapped the index
    b = fe.submit("lookup", [7], 1)
    assert a.status == "admitted" and b.status == "admitted"
    fe.batcher.stop()


def test_overlong_query_is_exact_miss_without_device():
    fe = make_frontend(queue_budget=64)
    t = fe.submit("lookup", list(range(1, SIGMA + 2)), SIGMA + 1)
    assert t.status == "admitted" and int(t.future.result(0)) == 0
    row = fe.submit("topk", list(range(1, SIGMA + 1)), SIGMA, k=4)
    np.testing.assert_array_equal(row.future.result(0),
                                  np.zeros(2 + 8, np.uint32))
    assert fe.batcher.depth == 0                 # nothing queued
    fe.batcher.stop()


# --------------------------------------------------------------------------- #
# end to end over localhost HTTP, vs the direct-call oracle
# --------------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def served():
    from repro.core.stats import NGramConfig
    from repro.serve.frontend import QueryFrontend
    from repro.serve.http import serve_http

    rng = np.random.default_rng(7)
    tokens = rng.integers(1, VOCAB + 1, 1500).astype(np.int32)
    cfg = NGramConfig(sigma=SIGMA, tau=1, vocab_size=VOCAB)
    svc = StreamingNGramService(cfg, cache_capacity=4096)
    svc.ingest(tokens)
    fe = QueryFrontend(svc, deadline_s=0.002)
    srv = serve_http(fe, "127.0.0.1", 0, block=False)
    try:
        yield svc, fe, srv.server_address
    finally:
        srv.shutdown()
        srv.server_close()
        fe.close()


def _post(addr, path, body, headers=None):
    conn = http.client.HTTPConnection(*addr, timeout=30)
    try:
        conn.request("POST", path, body=json.dumps(body),
                     headers={"Content-Type": "application/json",
                              **(headers or {})})
        r = conn.getresponse()
        return r.status, json.loads(r.read())
    finally:
        conn.close()


def _get(addr, path):
    conn = http.client.HTTPConnection(*addr, timeout=30)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, json.loads(r.read())
    finally:
        conn.close()


def test_http_lookup_matches_direct_calls(served):
    from repro.index.merge import segment_to_stats
    svc, _, addr = served
    stats = segment_to_stats(svc.gen.segments[0].to_segment())
    grams = np.asarray(stats.grams)[:40].astype(np.int32)
    lengths = np.asarray(stats.lengths)[:40].astype(np.int32)
    direct = svc.lookup(grams, lengths)
    # single-gram endpoint
    for i in range(0, 8):
        status, body = _post(addr, "/v1/lookup",
                             {"gram": grams[i, :lengths[i]].tolist()})
        assert status == 200
        assert body["count"] == int(direct[i])
    # batch endpoint, mixed with misses
    miss = [[29, 29, 29], [0]]
    status, body = _post(addr, "/v1/lookup", {
        "grams": [grams[i, :lengths[i]].tolist() for i in range(40)] + miss})
    assert status == 200
    assert body["counts"][:40] == [int(c) for c in direct]
    g_miss = np.zeros((2, SIGMA), np.int32)
    g_miss[0] = miss[0]
    g_miss[1, 0] = 0
    d_miss = svc.lookup(g_miss, np.array([3, 1], np.int32))
    assert body["counts"][40:] == [int(c) for c in d_miss]


def test_http_topk_matches_direct_calls(served):
    svc, _, addr = served
    for term in (1, 2, 5, 11, VOCAB):
        pg = np.zeros((1, SIGMA), np.int32)
        pg[0, 0] = term
        row = svc.continuations(pg, np.array([1], np.int32), k=4)[0]
        status, body = _post(addr, "/v1/topk", {"prefix": [term], "k": 4})
        assert status == 200
        assert body["n_distinct"] == int(row[0])
        assert body["total"] == int(row[1])
        assert body["terms"] == [int(t) for t in row[2:6]]
        assert body["counts"] == [int(c) for c in row[6:10]]


def test_http_sse_completion_matches_greedy_oracle(served):
    svc, _, addr = served
    prefix, steps, k = [3], 6, 4
    # direct-call greedy oracle
    want = []
    ctx = list(prefix)
    for _ in range(steps):
        w = ctx[-(SIGMA - 1):]
        pg = np.zeros((1, SIGMA), np.int32)
        pg[0, :len(w)] = w
        row = svc.continuations(pg, np.array([len(w)], np.int32), k=k)[0]
        term, count = int(row[2]), int(row[2 + k])
        if count == 0:
            break
        want.append((term, count))
        ctx.append(term)
    conn = http.client.HTTPConnection(*addr, timeout=30)
    try:
        conn.request("POST", "/v1/complete",
                     body=json.dumps({"prefix": prefix, "steps": steps,
                                      "k": k}))
        r = conn.getresponse()
        assert r.status == 200
        assert r.getheader("Content-Type") == "text/event-stream"
        raw = r.read().decode()
    finally:
        conn.close()
    events = [ln[6:] for ln in raw.split("\n") if ln.startswith("data: ")]
    assert events[-1] == "[DONE]"
    got = [(e["term"], e["count"]) for e in map(json.loads, events[:-1])]
    assert got == want


def test_http_topology_and_health(served):
    svc, fe, addr = served
    status, body = _get(addr, "/healthz")
    assert status == 200 and body == {"status": "ok"}
    status, topo = _get(addr, "/v1/system/topology")
    assert status == 200
    assert topo["service"]["generation"] == svc.gen.generation
    assert topo["index"]["kind"] == "generational"
    assert topo["index"]["n_segments"] == svc.gen.n_segments
    assert [s["rows"] for s in topo["index"]["segments"]] == \
        [ix.n_rows for ix in svc.gen.segments]
    assert topo["admission"]["queue_budget"] == fe.admission.queue_budget
    assert topo["batcher"]["buckets"] == list(fe.batcher.buckets)
    import jax
    assert topo["devices"] == {"backend": jax.default_backend(),
                               "kind": jax.devices()[0].device_kind,
                               "count": jax.device_count()}
    json.dumps(topo)                              # fully serializable


def test_http_error_paths(served):
    _, _, addr = served
    assert _get(addr, "/nope")[0] == 404
    assert _post(addr, "/v1/lookup", {"gram": "abc"})[0] == 400
    assert _post(addr, "/v1/lookup", {"gram": [1]},
                 headers={"X-Priority": "vip"})[0] == 400
    assert _post(addr, "/v1/topk", {"prefix": [1], "k": 0})[0] == 400


def test_http_shed_maps_to_503():
    from repro.serve.frontend import QueryFrontend
    from repro.serve.http import serve_http
    fe = QueryFrontend(stub_service(), executor=RecordingExecutor(),
                       admission=AdmissionController(queue_budget=0,
                                                     hard_limit=0),
                       deadline_s=10.0, autostart=False)
    srv = serve_http(fe, "127.0.0.1", 0, block=False)
    try:
        status, body = _post(srv.server_address, "/v1/lookup", {"gram": [1]})
        assert status == 503 and "shed" in body["error"]
    finally:
        srv.shutdown()
        srv.server_close()
        fe.batcher.stop()


def test_request_and_flush_spans_recorded(served):
    from repro.obs import trace as obs_trace
    _, _, addr = served
    tracer = obs_trace.enable_tracing()
    try:
        status, _ = _post(addr, "/v1/lookup", {"gram": [2, 4]})
        assert status == 200
    finally:
        obs_trace.disable_tracing()
    names = {e["name"] for e in tracer.export()["traceEvents"]}
    assert "serve.request" in names       # transport thread
    assert "serve.flush" in names         # batcher thread, same tracer


def test_launch_reexports_still_work():
    """The PR-5/PR-10 compatibility contract: every old import path holds."""
    from repro.launch import serve_ngrams as mod
    from repro.serve.cache import LRUQueryCache as new_cache
    assert mod.LRUQueryCache is new_cache
    assert mod.StreamingNGramService is StreamingNGramService
    from repro.serve.service import microbatch_drive, make_query_stream
    assert mod.microbatch_drive is microbatch_drive
    assert mod.make_query_stream is make_query_stream
    from repro.pipeline.executor import DoubleBufferedDriver
    assert mod.DoubleBufferedDriver is DoubleBufferedDriver
    with pytest.raises(AttributeError):
        mod.not_a_thing
