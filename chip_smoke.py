#!/usr/bin/env python3
"""Bring-up smoke run of the n-gram system on a TPU, through its entry points.

    python chip_smoke.py              # one chip: job, index/serving, frontend,
                                      # kernels
    python chip_smoke.py --chips 4    # four chips: mesh waves, mesh jobs and
                                      # the sharded index, each against one
                                      # device, and nothing else

Every phase calls the same library functions the CLIs call (``run_job``,
``WaveExecutor.run``, ``StreamingNGramService``, ``QueryFrontend`` +
``serve_http``, the ``kernels.ops`` wrappers) in this one process, checks what
comes out against a reference, and raises on the first mismatch.  Without a
TPU the script exits non-zero before any phase; it never falls back to the
CPU.  The last line of standard output is one JSON object naming the device.

Wall times printed per phase are smoke times (compilation included), not
benchmark figures.  The phase functions take their sizes as arguments, so the
test suite runs the same code at a tiny size on the CPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# the paper's language-model setting on the NYT profile (repro.data.corpus)
SIGMA, TAU = 5, 10
METHODS = ("suffix_sigma", "naive", "apriori_scan", "apriori_index")
TOPK = 8

# One chip.  A wave of 2**21 tokens compiles (fused sigma=5 program, described
# v5e, memory_analysis) to 0.32 GB of temporaries and 0.18 GB of outputs, so
# the wave engine's device state -- one wave running, two in flight -- stays
# far under half of the 16 GB HBM.  The same wave size serves the service's
# ingest, so both share one compiled program.
ORACLE_TOKENS = 200_000
JOB_TOKENS = 1 << 23
WAVE_TOKENS = 1 << 21
N_LOOKUPS = 4096
N_PREFIXES = 1024
KERNEL_ROWS = 1 << 20

# Four chips.
MESH_WAVE_CORPUS = 1 << 21
MESH_WAVE_TOKENS = 1 << 19
MESH_JOB_TOKENS = 1 << 20


class SmokeFailure(RuntimeError):
    """A phase produced something its reference disagrees with."""


def _require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _cfg(method: str = "suffix_sigma"):
    from repro.core import NGramConfig
    from repro.data.corpus import NYT
    return NGramConfig(sigma=SIGMA, tau=TAU, vocab_size=NYT.vocab_size,
                       method=method)


def corpus(n_tokens: int, seed: int):
    """Exactly ``n_tokens`` NYT-profile tokens (PAD-separated sentences)."""
    from repro.data import corpus as corpus_mod
    toks = corpus_mod.zipf_corpus(n_tokens, corpus_mod.NYT, seed=seed,
                                  duplicate_frac=0.02)
    return toks[:n_tokens]


# ------------------------------------------------------------ host references
def _row_keys(grams, lengths=None):
    """[N] byte-comparable keys of zero-padded gram rows (optionally led by
    a length column)."""
    import numpy as np
    from repro.index._layout import row_bytes_view
    cols = np.asarray(grams, np.int64).astype(np.uint32)
    if lengths is not None:
        cols = np.concatenate(
            [np.asarray(lengths).astype(np.uint32)[:, None], cols], axis=1)
    return row_bytes_view(cols)


def _same_stats(a, b, what: str) -> None:
    import numpy as np
    for field in ("grams", "lengths", "counts"):
        _require(np.array_equal(getattr(a, field), getattr(b, field)),
                 f"{what}: {field} differ")


def check_invariants(tokens, stats, *, tau: int, vocab_size: int) -> None:
    """Exact properties of a frequent-n-gram set, checked on the host.

    Unigram counts are ``np.bincount`` of the tokens wherever that is >= tau,
    and every n-gram's cf is at most its (n-1)-prefix's cf (which must be
    present: APRIORI)."""
    import numpy as np
    grams, lengths = stats.grams, stats.lengths
    counts = np.asarray(stats.counts, np.int64)
    bc = np.bincount(np.asarray(tokens), minlength=vocab_size + 1)
    bc[0] = 0
    uni = lengths == 1
    order = np.argsort(grams[uni, 0])
    want = np.flatnonzero(bc >= tau)
    _require(np.array_equal(grams[uni, 0][order], want),
             "unigram set != bincount >= tau")
    _require(np.array_equal(counts[uni][order], bc[want]),
             "unigram cf != bincount")
    keys = _row_keys(grams)
    by_key = np.argsort(keys)
    sorted_keys = keys[by_key]
    multi = np.flatnonzero(lengths >= 2)
    pref = grams[multi].copy()
    pref[np.arange(multi.size), lengths[multi] - 1] = 0
    pk = _row_keys(pref)
    pos = np.minimum(np.searchsorted(sorted_keys, pk), keys.size - 1)
    _require(bool(np.all(sorted_keys[pos] == pk)),
             "an n-gram's (n-1)-prefix is missing")
    _require(bool(np.all(counts[multi] <= counts[by_key[pos]])),
             "an n-gram's cf exceeds its prefix's cf")


def lookup_queries(stats, n: int, *, sigma: int, vocab_size: int, seed: int):
    """``n`` stored grams (sampled rows) then ``n`` absent ones, with their
    expected counts: (grams [2n, sigma], lengths [2n], want [2n])."""
    import numpy as np
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, len(stats), n)
    stored_g = stats.grams[rows].astype(np.int32)
    stored_l = stats.lengths[rows].astype(np.int32)
    keys = np.sort(_row_keys(stats.grams))
    absent_g, absent_l = [], []
    while sum(len(a) for a in absent_l) < n:
        ln = rng.integers(1, sigma + 1, 2 * n).astype(np.int32)
        g = rng.integers(1, vocab_size + 1, (2 * n, sigma)).astype(np.int32)
        g *= np.arange(sigma)[None, :] < ln[:, None]
        q = _row_keys(g)
        pos = np.minimum(np.searchsorted(keys, q), keys.size - 1)
        fresh = keys[pos] != q
        absent_g.append(g[fresh])
        absent_l.append(ln[fresh])
    absent_g = np.concatenate(absent_g)[:n]
    absent_l = np.concatenate(absent_l)[:n]
    want = np.concatenate([np.asarray(stats.counts)[rows],
                           np.zeros(n, np.int64)])
    return (np.concatenate([stored_g, absent_g]),
            np.concatenate([stored_l, absent_l]), want)


def topk_queries(stats, n: int, *, seed: int):
    """``n`` prefixes of stored grams of length >= 2: (prefixes, p_len)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    long_rows = np.flatnonzero(stats.lengths >= 2)
    rows = rng.choice(long_rows, n)
    p_len = (stats.lengths[rows] - 1).astype(np.int32)
    g = stats.grams[rows].astype(np.int32)
    g *= np.arange(g.shape[1])[None, :] < p_len[:, None]
    return g, p_len


def topk_reference(stats, prefixes, p_len, *, k: int):
    """numpy top-k continuations: rows [Q, 2 + 2k] = nd | total | terms | cfs,
    ranked (cf desc, term asc)."""
    import numpy as np
    lengths = stats.lengths
    counts = np.asarray(stats.counts, np.int64)
    sigma = stats.grams.shape[1]
    rows = np.flatnonzero(lengths >= 1)
    plen = lengths[rows] - 1
    pg = stats.grams[rows].copy()
    pg[np.arange(rows.size), plen] = 0
    last = stats.grams[rows, plen]
    pkey = _row_keys(pg, plen)
    uniq, inv = np.unique(pkey, return_inverse=True)
    order = np.lexsort((last, -counts[rows], inv))
    inv_s = inv[order]
    starts = np.searchsorted(inv_s, np.arange(uniq.size))
    ends = np.searchsorted(inv_s, np.arange(uniq.size), side="right")
    cf_s = counts[rows][order]
    last_s = last[order]
    q = np.asarray(prefixes)[:, :sigma] * (
        np.arange(sigma)[None, :] < np.asarray(p_len)[:, None])
    qkey = _row_keys(q, p_len)
    out = np.zeros((q.shape[0], 2 + 2 * k), np.int64)
    gid = np.minimum(np.searchsorted(uniq, qkey), uniq.size - 1)
    for i in range(q.shape[0]):
        if uniq[gid[i]] != qkey[i]:
            continue
        s, e = starts[gid[i]], ends[gid[i]]
        top = min(k, e - s)
        out[i, 0] = e - s
        out[i, 1] = cf_s[s:e].sum()
        out[i, 2:2 + top] = last_s[s:s + top]
        out[i, 2 + k:2 + k + top] = cf_s[s:s + top]
    return out


def _topk_rows(res, k: int):
    import numpy as np
    nd, tot, terms, cfs = (np.asarray(x).astype(np.int64) for x in res)
    return np.concatenate([nd[:, None], tot[:, None], terms[:, :k],
                           cfs[:, :k]], axis=1)


# ------------------------------------------------------------------- phases
def phase_methods(n_tokens: int) -> dict:
    """The four methods, monolithic, against the pure-Python oracle."""
    from repro.core import oracle, run_job
    toks = corpus(n_tokens, seed=1)
    want = oracle.ngram_counts(toks, SIGMA, TAU)
    for m in METHODS:
        got = run_job(toks, _cfg(m)).to_dict()
        _require(got == want, f"{m} differs from the oracle")
    return {"tokens": int(toks.size), "grams": len(want)}


def phase_job(n_tokens: int, wave_tokens: int):
    """Suffix-sigma through ``WaveExecutor`` against the monolithic job (its
    program fits at the full corpus) and the host invariants.  Returns
    (tokens, stats, info)."""
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np
    from repro.core import run_job
    from repro.pipeline import WaveExecutor
    cfg = _cfg()
    toks = corpus(n_tokens, seed=0)
    # the monolithic job compiles and runs beside the waves
    with ThreadPoolExecutor(1) as side:
        mono = side.submit(run_job, toks, cfg)
        stats = WaveExecutor(cfg, wave_tokens=wave_tokens).run(toks)
        mono = mono.result()
    n_waves = -(-toks.size // wave_tokens)
    _require(stats.counters["waves"] == n_waves,
             f"{stats.counters['waves']} waves, expected {n_waves}")
    _same_stats(stats, mono, "waves vs monolithic")
    check_invariants(toks, stats, tau=TAU, vocab_size=cfg.vocab_size)
    return toks, stats, {"tokens": int(toks.size), "waves": n_waves,
                         "grams": len(stats),
                         "max_cf": int(np.max(stats.counts))}


def ingest_deltas(tokens, wave_tokens: int) -> list:
    """Four deltas for the service: the bulk of the corpus, then three of a
    quarter wave each.  The bulk runs in the job's wave shape and the three
    small deltas share one, so the ingest adds one wave program, not one per
    delta size; the small deltas compact into a merged (compressed) rung
    beside the bulk."""
    import numpy as np
    small = wave_tokens // 4
    first = tokens.size - 3 * small
    return np.split(tokens, [first, first + small, first + 2 * small])


def _check_index(name, idx, qg, ql, want, pg, pl, want_topk) -> None:
    import numpy as np
    from repro import index as ix
    got = np.asarray(ix.lookup(idx, qg, ql)).astype(np.int64)
    _require(np.array_equal(got, want), f"{name}: lookups differ")
    rows = _topk_rows(ix.continuations(idx, pg, pl, k=TOPK), TOPK)
    _require(np.array_equal(rows, want_topk), f"{name}: top-k differs")


def phase_index(tokens, stats, *, wave_tokens: int, n_lookups: int,
                n_prefixes: int):
    """Flat and compressed indexes frozen from the job, then the corpus
    ingested into ``StreamingNGramService`` in deltas through the wave path.
    Returns (service, its reference stats, info)."""
    import numpy as np
    from repro import index as ix
    from repro.index.compress import CompressedNGramIndex
    from repro.pipeline import WaveExecutor
    from repro.serve.service import StreamingNGramService
    cfg = _cfg()
    v = cfg.vocab_size
    flat = ix.build_index(stats, vocab_size=v)
    comp = ix.build_compressed_index(stats, vocab_size=v)
    qg, ql, want = lookup_queries(stats, n_lookups, sigma=SIGMA,
                                  vocab_size=v, seed=2)
    pg, pl = topk_queries(stats, n_prefixes, seed=2)
    want_topk = topk_reference(stats, pg, pl, k=TOPK)
    _check_index("flat", flat, qg, ql, want, pg, pl, want_topk)
    _check_index("compressed", comp, qg, ql, want, pg, pl, want_topk)

    svc = StreamingNGramService(cfg, compress=True, wave_tokens=wave_tokens)
    parts = []
    for delta in ingest_deltas(tokens, wave_tokens):
        svc.ingest(delta)
        parts.append(WaveExecutor(cfg, wave_tokens=wave_tokens).run(delta))
    ref = ix.stats_union(*parts)
    segs = svc.gen.segments
    n_comp = sum(isinstance(s, CompressedNGramIndex) for s in segs)
    _require(n_comp >= 1, "no compressed rung in the generational stack")
    qg, ql, want = lookup_queries(ref, n_lookups, sigma=SIGMA, vocab_size=v,
                                  seed=3)
    got = svc.lookup(qg, ql).astype(np.int64)
    _require(np.array_equal(got, want), "service: lookups differ")
    pg, pl = topk_queries(ref, n_prefixes, seed=3)
    got = svc.continuations(pg, pl, k=TOPK).astype(np.int64)
    _require(np.array_equal(got, topk_reference(ref, pg, pl, k=TOPK)),
             "service: top-k differs")
    return svc, ref, {
        "flat_bytes": int(flat.nbytes), "compressed_bytes": int(comp.nbytes),
        "segments": len(segs), "compressed_segments": n_comp,
        "service_rows": int(svc.gen.n_rows), "service_bytes":
        int(svc.gen.nbytes)}


def _http(port: int, path: str, body: dict):
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = resp.read()
        _require(resp.status == 200, f"{path}: HTTP {resp.status} {data!r}")
        return data
    finally:
        conn.close()


def phase_frontend(svc, ref, *, n_requests: int = 8) -> dict:
    """``/v1/lookup``, ``/v1/topk`` and ``/v1/complete`` over HTTP, each equal
    to the reference and to a direct service call."""
    import numpy as np
    from repro.serve.frontend import QueryFrontend
    from repro.serve.http import serve_http
    v = svc.cfg.vocab_size
    qg, ql, want = lookup_queries(ref, n_requests, sigma=SIGMA, vocab_size=v,
                                  seed=4)
    pg, pl = topk_queries(ref, n_requests, seed=4)
    want_topk = topk_reference(ref, pg, pl, k=TOPK)
    with QueryFrontend(svc) as fe:
        srv = serve_http(fe, "127.0.0.1", 0, block=False)
        try:
            port = srv.server_address[1]
            got = [json.loads(_http(port, "/v1/lookup", {
                "gram": [int(t) for t in g[:n]]}))["count"]
                for g, n in zip(qg, ql)]
            batch = json.loads(_http(port, "/v1/lookup", {
                "grams": [[int(t) for t in g[:n]] for g, n in zip(qg, ql)],
                "lengths": [int(n) for n in ql]}))["counts"]
            topk = []
            for g, n in zip(pg, pl):
                r = json.loads(_http(port, "/v1/topk", {
                    "prefix": [int(t) for t in g[:n]], "k": TOPK}))
                topk.append([r["n_distinct"], r["total"]] + r["terms"]
                            + r["counts"])
            prefix = [int(t) for t in pg[0][:pl[0]]]
            events = _http(port, "/v1/complete",
                           {"prefix": prefix, "steps": 6, "k": 4})
        finally:
            srv.shutdown()
            srv.server_close()
    _require(np.array_equal(got, want), "HTTP lookups differ")
    _require(np.array_equal(batch, want), "HTTP batch lookup differs")
    _require(np.array_equal(svc.lookup(qg, ql), want),
             "direct lookups differ")
    _require(np.array_equal(np.asarray(topk), want_topk), "HTTP top-k differs")
    _require(np.array_equal(svc.continuations(pg, pl, k=TOPK), want_topk),
             "direct top-k differs")
    lines = [ln[len(b"data: "):] for ln in events.split(b"\n\n")
             if ln.startswith(b"data: ")]
    _require(lines and lines[-1] == b"[DONE]", "SSE stream did not finish")
    steps = [json.loads(ln) for ln in lines[:-1]]
    ctx = list(prefix)
    for i, ev in enumerate(steps):
        win = ctx[-(SIGMA - 1):]
        g = np.zeros((1, SIGMA), np.int32)
        g[0, :len(win)] = win
        row = svc.continuations(g, np.asarray([len(win)], np.int32), k=4)[0]
        _require(ev == {"step": i, "term": int(row[2]), "count": int(row[6])},
                 f"SSE step {i} differs from the direct call")
        ctx.append(int(row[2]))
    return {"requests": 2 * n_requests + 2, "sse_steps": len(steps)}


def phase_kernels(n_rows: int) -> dict:
    """Each kernel the TPU compiler accepts, once, against ``kernels/ref``."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    rng = np.random.default_rng(5)
    v = _cfg().vocab_size
    terms = rng.integers(0, 6, (n_rows, SIGMA)).astype(np.int32)
    terms = jnp.asarray(terms[np.lexsort(terms.T[::-1])])
    toks = jnp.asarray(rng.integers(0, v + 1, n_rows).astype(np.int32))
    keys = jnp.asarray(rng.integers(0, 2**32, n_rows, dtype=np.uint64)
                       .astype(np.uint32))
    valid = jnp.asarray(rng.random(n_rows) < 0.9)
    pairs = {
        "lcp_boundary": (ops.lcp_boundary(terms),
                         ref.lcp_boundary_ref(terms)),
        "suffix_pack": (ops.suffix_pack(toks, sigma=SIGMA, vocab_size=v),
                        ref.suffix_pack_ref(toks, sigma=SIGMA, vocab_size=v)),
        "hash_partition": (ops.hash_partition(keys, valid, n_parts=64),
                           ref.hash_partition_ref(keys, valid, 64)),
    }
    for name, (got, want) in pairs.items():
        got = got if isinstance(got, tuple) else (got,)
        want = want if isinstance(want, tuple) else (want,)
        for g, w in zip(got, want):
            _require(np.array_equal(np.asarray(g), np.asarray(w)),
                     f"kernel {name} differs from its reference")
    return {"kernels": sorted(pairs), "rows": n_rows}


def phase_mesh_waves(mesh, *, wave_corpus: int, wave_tokens: int):
    """``WaveExecutor`` over the mesh against the one-device wave run (which
    compiles and runs beside it).  Returns (stats, info)."""
    from concurrent.futures import ThreadPoolExecutor
    from repro.pipeline import WaveExecutor
    cfg = _cfg()
    toks = corpus(wave_corpus, seed=6)
    with ThreadPoolExecutor(1) as side:
        single = side.submit(WaveExecutor(cfg, wave_tokens=wave_tokens).run,
                             toks)
        dist = WaveExecutor(cfg, wave_tokens=wave_tokens, mesh=mesh).run(toks)
        single = single.result()
    _same_stats(dist, single, "mesh waves vs one device")
    return single, {"tokens": int(toks.size), "grams": len(single),
                    "waves": int(dist.counters["waves"])}


def phase_mesh_index(mesh, stats, *, n_lookups: int,
                     n_prefixes: int) -> dict:
    """``build_sharded_index`` + ``serve_queries`` against one-device lookups
    and top-k over the same job output."""
    import numpy as np
    from repro import index as ix
    v = _cfg().vocab_size
    sharded = ix.build_sharded_index(stats, vocab_size=v, mesh=mesh)
    local = ix.build_index(stats, vocab_size=v)
    qg, ql, want = lookup_queries(stats, n_lookups, sigma=SIGMA,
                                  vocab_size=v, seed=7)
    got = np.asarray(ix.serve_queries(sharded, qg, ql)).astype(np.int64)
    _require(np.array_equal(np.asarray(ix.lookup(local, qg, ql)), want),
             "one-device lookups differ")
    _require(np.array_equal(got, want), "sharded lookups differ")
    pg, pl = topk_queries(stats, n_prefixes, seed=7)
    got = np.asarray(ix.serve_queries(sharded, pg, pl, mode="continuations",
                                      k=TOPK)).astype(np.int64)
    want = _topk_rows(ix.continuations(local, pg, pl, k=TOPK), TOPK)
    _require(np.array_equal(want, topk_reference(stats, pg, pl, k=TOPK)),
             "one-device top-k differs from the reference")
    _require(np.array_equal(got, want), "sharded top-k differs")
    return {"rows": len(stats), "lookups": int(qg.shape[0]),
            "prefixes": int(pg.shape[0])}


def phase_mesh_jobs(mesh, *, job_tokens: int, report=None) -> dict:
    """``run_job(mesh=...)`` for each method against the monolithic job.

    The monolithic reference is the suffix-sigma job (the one-chip run holds
    all four monolithic methods equal to the oracle); it compiles and runs
    beside the mesh jobs, which run one after another."""
    from concurrent.futures import ThreadPoolExecutor
    from repro.core import run_job
    from repro.pipeline.stages import canonical_stats
    toks = corpus(job_tokens, seed=8)
    with ThreadPoolExecutor(1) as side:
        mono = side.submit(run_job, toks, _cfg())
        for m in METHODS:
            got = canonical_stats(run_job(toks, _cfg(m), mesh=mesh))
            _same_stats(got, mono.result(), f"{m} on the mesh vs monolithic")
            if report:
                report(m)
    return {"tokens": int(toks.size), "grams": len(mono.result()),
            "methods": list(METHODS)}


# --------------------------------------------------------------------- main
def _tpu_or_exit(chips: int):
    """The device gate: a TPU backend with at least ``chips`` chips."""
    import jax
    backend = jax.default_backend()
    devices = jax.devices()
    if backend != "tpu" or devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (backend {backend!r}); refusing to run "
              "on another platform", file=sys.stderr)
        sys.exit(2)
    if len(devices) < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} TPU chips, found "
              f"{len(devices)}", file=sys.stderr)
        sys.exit(2)
    return devices


def _compile_counter() -> dict:
    """Compile requests, their seconds, and how many the persistent cache
    answered (the backend-compile event fires for those too)."""
    import jax
    seen = {"n": 0, "s": 0.0, "hits": 0}

    def on_duration(event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["n"] += 1
            seen["s"] += secs

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return seen


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed paths on a 4-chip "
                         "mesh, each against one device")
    args = ap.parse_args(argv)
    import jax
    devices = _tpu_or_exit(args.chips)
    dev = devices[0]
    print(f"# device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}", flush=True)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}", flush=True)
    compiles = _compile_counter()

    t_start = time.perf_counter()

    def run(name, fn, *a, **kw):
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        info = out[-1] if isinstance(out, tuple) else out
        note(f"phase {name}: ok, {time.perf_counter() - t0:.1f} s smoke "
             f"time, {json.dumps(info)}")
        return out

    def note(msg):
        print(f"# [{time.perf_counter() - t_start:7.1f} s] {msg}", flush=True)

    if args.chips == 4:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(4)
        stats, _ = run("mesh waves", phase_mesh_waves, mesh,
                       wave_corpus=MESH_WAVE_CORPUS,
                       wave_tokens=MESH_WAVE_TOKENS)
        run("sharded index", phase_mesh_index, mesh, stats,
            n_lookups=N_LOOKUPS, n_prefixes=N_PREFIXES)
        run("mesh jobs", phase_mesh_jobs, mesh, job_tokens=MESH_JOB_TOKENS,
            report=lambda m: note(f"mesh job {m}: equal to monolithic"))
    else:
        # the oracle check and the kernels compile and run beside the job
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(2) as side:
            methods = side.submit(run, "methods", phase_methods,
                                  ORACLE_TOKENS)
            kernels = side.submit(run, "kernels", phase_kernels, KERNEL_ROWS)
            toks, stats, _ = run("job", phase_job, JOB_TOKENS, WAVE_TOKENS)
            svc, ref, _ = run("index", phase_index, toks, stats,
                              wave_tokens=WAVE_TOKENS, n_lookups=N_LOOKUPS,
                              n_prefixes=N_PREFIXES)
            run("frontend", phase_frontend, svc, ref)
            kernels.result()
            methods.result()
    note(f"all phases ok; {compiles['n']} compiles ({compiles['hits']} from "
         f"the persistent cache) took {compiles['s']:.1f} s summed over "
         "threads")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
