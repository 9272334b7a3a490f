"""The query service: generational index + cache behind a batch lookup API.

``StreamingNGramService`` (moved out of ``launch/serve_ngrams.py``, which
keeps lazy re-exports) owns one :class:`~repro.index.merge.GenerationalIndex`
and one :class:`~repro.serve.cache.LRUQueryCache` and exposes the three
operations every frontend layer composes:

  * ``ingest(tokens)``        -- job on the delta -> fresh L0 segment swap
  * ``lookup(grams, lengths)``-- batched point counts (cache-first)
  * ``continuations(...)``    -- batched top-k completion rows (cache-first)

plus the split ``_submit_lookup`` / ``_collect_lookup`` pair the
double-buffered paths (``lookup_pipelined`` here, the continuous batcher in
:mod:`repro.serve.batcher`) ride to overlap host work with device execution.

``microbatch_drive`` and ``make_query_stream`` are the synthetic-workload
helpers the CLI drivers and benchmarks share; they live with the service so
the launch script stays a thin argument-parsing shell.

All jax-touching imports are deferred into the methods: importing this module
must not initialize the backend (the ``--devices`` drivers set ``XLA_FLAGS``
first).
"""
from __future__ import annotations

import time

from .cache import LRUQueryCache

__all__ = ["StreamingNGramService", "microbatch_drive", "make_query_stream"]


def make_query_stream(stats, *, n_queries: int, sigma: int, vocab_size: int,
                      miss_frac: float, seed: int = 0):
    """(grams [N, sigma], lengths [N]): sampled index rows + uniform-random misses.

    Hits are drawn cf-weighted (hot grams are queried more -- the serving-load
    analogue of the corpus Zipf skew the shuffle partitioner absorbs)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    grams = np.zeros((n_queries, sigma), np.int32)
    lengths = np.zeros((n_queries,), np.int32)
    n_rows = len(stats)
    is_miss = rng.random(n_queries) < miss_frac
    if n_rows:
        p = np.asarray(stats.counts, np.float64)
        p = p / p.sum()
        rows = rng.choice(n_rows, size=n_queries, p=p)
        grams = np.asarray(stats.grams)[rows].astype(np.int32)
        lengths = np.asarray(stats.lengths)[rows].astype(np.int32)
    miss_len = rng.integers(1, sigma + 1, n_queries).astype(np.int32)
    miss_g = rng.integers(1, vocab_size + 1, (n_queries, sigma)).astype(np.int32)
    miss_g *= np.arange(sigma)[None, :] < miss_len[:, None]
    grams = np.where(is_miss[:, None], miss_g, grams)
    lengths = np.where(is_miss, miss_len, lengths)
    return grams, lengths


class StreamingNGramService:
    """Generational index + query cache behind a batch lookup/completion API.

    ``ingest`` streams new document tokens through the ordinary SUFFIX-sigma
    job phases into a fresh L0 segment (``GenerationalIndex.ingest`` handles
    the size-tiered merges); queries between swaps hit the LRU cache first and
    only the residual miss rows go to the device, padded to a power-of-two
    sub-batch so the compiled-program cache stays small.
    """

    #: cache/coalescing key of one point lookup -- shared with the frontend's
    #: in-flight duplicate coalescing, which must key identically
    @staticmethod
    def lookup_key(gram, length: int):
        return (int(length), gram[:max(int(length), 0)].tobytes())

    #: cache/coalescing key of one top-k continuation query
    @staticmethod
    def continuation_key(gram, length: int, k: int):
        return ("c", int(k), int(length), gram[:max(int(length), 0)].tobytes())

    def __init__(self, cfg, *, compress: bool = False, block_size: int = 4,
                 use_kernels: bool = False, cache_capacity: int = 65536,
                 size_ratio: int = 4, wave_tokens: int | None = None, mesh=None,
                 axis_name: str = "data", overlap: bool = True):
        from repro.index import GenerationalIndex
        self.cfg = cfg
        self.use_kernels = use_kernels
        self.wave_tokens = wave_tokens
        self.mesh = mesh
        self.axis_name = axis_name
        self.overlap = overlap
        self.gen = GenerationalIndex(
            sigma=cfg.sigma, vocab_size=cfg.vocab_size, compress=compress,
            block_size=block_size, size_ratio=size_ratio)
        self.cache = LRUQueryCache(cache_capacity)
        self._wave_ex = None

    def ingest(self, tokens) -> dict:
        """Run the job phases over a token delta and swap the new L0 in.

        With ``wave_tokens`` set, the delta streams through the wave engine
        (``repro.pipeline.WaveExecutor``) instead of one monolithic job: the
        device only ever holds one wave of job state, so a delta (or an
        initial corpus) larger than device memory ingests end to end.  A
        ``mesh`` shards the work over its devices -- each wave's stage
        pipeline when waves are on, the ordinary distributed job otherwise.
        The resulting stats are bit-identical every way.
        """
        from repro.obs import metrics as obs_metrics
        from repro.obs import trace as obs_trace
        with obs_trace.span("svc.ingest") as sp:
            t0 = time.perf_counter()
            if self.wave_tokens is not None:
                if self._wave_ex is None:  # reuse: compiled programs carry over
                    from repro.pipeline import WaveExecutor
                    self._wave_ex = WaveExecutor(self.cfg,
                                                 wave_tokens=self.wave_tokens,
                                                 mesh=self.mesh,
                                                 axis_name=self.axis_name,
                                                 overlap=self.overlap)
                stats = self._wave_ex.run(tokens)
            else:
                from repro.core import run_job
                stats = run_job(tokens, self.cfg, mesh=self.mesh,
                                axis_name=self.axis_name)
            t_job = time.perf_counter() - t0
            obs_metrics.get_registry().merge_job_counters(stats.counters)
            t0 = time.perf_counter()
            report = self.gen.ingest(stats)
            report.update(job_s=t_job, ingest_s=time.perf_counter() - t0,
                          segments=self.gen.n_segments,
                          waves=stats.counters.get("waves", 1))
            if sp:
                sp.set(tokens=len(tokens), rows=report.get("ingested_rows"),
                       waves=report["waves"])
        return report

    def _submit_lookup(self, grams, lengths) -> dict:
        """Cache consult + async device dispatch of the miss rows.

        The returned record holds the *unmaterialized* device result; pairing
        ``_submit_lookup`` of batch i+1 with ``_collect_lookup`` of batch i is
        the double-buffered hot path (cache fill rides the collect side, one
        batch behind the device)."""
        import numpy as np
        g = np.asarray(grams, np.int32)
        ln = np.asarray(lengths, np.int32)
        gen_id = self.gen.generation
        out = np.zeros((g.shape[0],), np.uint32)
        miss = []
        keys = []
        for i in range(g.shape[0]):
            key = self.lookup_key(g[i], int(ln[i]))
            v = self.cache.get(key, gen_id)
            if v is None:
                miss.append(i)
                keys.append(key)
            else:
                out[i] = v
        dev, pad = None, 0
        if miss:
            from repro.index.query import lookup_deferred
            m = len(miss)
            pad = max(1 << (m - 1).bit_length(), 16)
            mg = np.zeros((pad, g.shape[1]), np.int32)
            mln = np.zeros((pad,), np.int32)
            mg[:m] = g[miss]
            mln[:m] = ln[miss]
            # per-segment deferred dispatches: nothing is materialized here,
            # even with several live generations
            dev = lookup_deferred(self.gen, mg, mln,
                                  use_kernels=self.use_kernels)
        return {"out": out, "miss": miss, "keys": keys, "dev": dev,
                "pad": pad, "gen": gen_id}

    def _collect_lookup(self, rec: dict):
        if rec["dev"] is not None:
            from repro.index.query import collect_lookup
            cf = collect_lookup(rec["dev"], rec["pad"])[:len(rec["miss"])]
            rec["out"][rec["miss"]] = cf
            for key, v in zip(rec["keys"], cf):
                self.cache.put(key, rec["gen"], int(v))
        return rec["out"]

    def lookup(self, grams, lengths):
        """Point counts [B] uint32; cache hits never touch the device."""
        return self._collect_lookup(self._submit_lookup(grams, lengths))

    def lookup_pipelined(self, batches) -> list:
        """Drive (grams, lengths) batches double-buffered: batch i+1 is
        dispatched before batch i's device result is materialized, so host
        batching/cache work overlaps device execution with no
        ``block_until_ready`` anywhere."""
        from repro.obs import metrics as obs_metrics
        from repro.obs import trace as obs_trace
        from repro.pipeline.executor import DoubleBufferedDriver
        drv = DoubleBufferedDriver(self._submit_lookup,
                                   collect=self._collect_lookup)
        reg = obs_metrics.get_registry()
        inflight = reg.gauge("serve.inflight")
        results: list = []
        with obs_trace.span("serve.pipelined") as sp:
            for g, ln in batches:
                inflight.add(1)               # one submitted, maybe one live
                res, _ = drv.submit(g, ln)
                if res is not None:
                    inflight.add(-1)
                    results.append(res)
            res, _ = drv.drain()
            inflight.set(0)
            if res is not None:
                results.append(res)
            if sp:
                sp.set(batches=len(batches))
        return results

    def continuations(self, prefixes, p_len, *, k: int = 8):
        """Top-k completion rows [B, 2+2k] uint32 (nd | total | terms | cfs)."""
        import numpy as np
        from repro.index import continuations as idx_cont
        pg = np.asarray(prefixes, np.int32)
        pl = np.asarray(p_len, np.int32)
        gen_id = self.gen.generation
        out = np.zeros((pg.shape[0], 2 + 2 * k), np.uint32)
        miss = []
        for i in range(pg.shape[0]):
            key = self.continuation_key(pg[i], int(pl[i]), k)
            v = self.cache.get(key, gen_id)
            if v is None:
                miss.append(i)
            else:
                out[i] = v
        if miss:
            m = len(miss)
            pad = max(1 << (m - 1).bit_length(), 16)
            mg = np.zeros((pad, pg.shape[1]), np.int32)
            mln = np.zeros((pad,), np.int32)
            mg[:m] = pg[miss]
            mln[:m] = pl[miss]
            nd, tot, terms, cfs = [np.asarray(x) for x in idx_cont(
                self.gen, mg, mln, k=k, use_kernels=self.use_kernels)]
            rows = np.concatenate([nd[:m, None], tot[:m, None], terms[:m],
                                   cfs[:m]], axis=1).astype(np.uint32)
            out[miss] = rows
            for j, i in enumerate(miss):
                key = self.continuation_key(pg[i], int(pl[i]), k)
                self.cache.put(key, gen_id, rows[j])
        return out


def microbatch_drive(answer, grams, lengths, batch: int, *, warmup: int = 2,
                     hist_name: str = "drive.batch_seconds"):
    """Feed the stream through ``answer`` in fixed micro-batches; (qps, lat[s]).

    Timed batches also land in the ``hist_name`` registry histogram, so the
    p50/p95/p99 the production frontend needs come out of the metrics export
    as well as the returned sample list.
    """
    import numpy as np
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    n = grams.shape[0]
    n_batches = -(-n // batch)
    pad = n_batches * batch - n
    g = np.pad(grams, ((0, pad), (0, 0)))
    ln = np.pad(lengths, (0, pad))
    for i in range(min(warmup, n_batches)):      # compile + cache warm
        answer(g[i * batch:(i + 1) * batch], ln[i * batch:(i + 1) * batch])
    hist = obs_metrics.get_registry().histogram(hist_name)
    lat = []
    with obs_trace.span("serve.drive") as sp:
        t_all = time.perf_counter()
        for i in range(n_batches):
            t0 = time.perf_counter()
            answer(g[i * batch:(i + 1) * batch], ln[i * batch:(i + 1) * batch])
            dt = time.perf_counter() - t0
            lat.append(dt)
            hist.observe(dt)
        qps = n / (time.perf_counter() - t_all)
        if sp:
            sp.set(batch=batch, n_batches=n_batches, qps=int(qps))
    return qps, lat
