"""NAIVE (Algorithm 1): word counting extended to all n-grams up to sigma.

The map phase emits *every* n-gram occurrence -- O(|d| * sigma) records of O(sigma)
bytes per document, the paper's worst case and the reason the method drowns in
shuffle traffic for large sigma (Figs 4-5).  The reduce phase is a plain
count-per-distinct-gram.  Partitioning hashes the whole gram (any reducer may count
any gram -- no locality requirement, unlike SUFFIX-sigma).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.mapreduce import pack as packing
from repro.mapreduce import shuffle as shf
from repro.pipeline import plan as plan_mod
from .common import count_exact_grams, gram_hash
from .stats import NGramConfig, NGramStats
from .suffix_sigma import suffix_windows


def _explode(tokens: jax.Array, sigma: int, vocab_size: int):
    """Map emit: all (position, length<=sigma) n-grams.  [N*sigma, W] records."""
    n = tokens.shape[0]
    windows, _ = suffix_windows(tokens, sigma)                     # [N, sigma]
    lmask = jnp.tril(jnp.ones((sigma, sigma), jnp.int32))          # [len, sigma]
    grams = windows[:, None, :] * lmask[None, :, :]                # [N, len, sigma]
    valid = windows != 0           # windows are PAD-masked, so col l != 0 <=> len > l
    grams = (grams * valid[:, :, None]).reshape(n * sigma, sigma)
    lanes = packing.pack_terms(grams, vocab_size=vocab_size)
    w = valid.reshape(-1).astype(jnp.uint32)
    return jnp.concatenate([lanes, w[:, None]], axis=1), valid.reshape(-1)


def _plan_emit(tok_ext, aux_ext, n_live, cfg: NGramConfig, carry, k):
    """Map emit: every (position, length<=sigma) n-gram of the window.  Row
    ``i`` belongs to position ``i // sigma``; halo positions emit nothing."""
    records, valid = _explode(tok_ext, cfg.sigma, cfg.vocab_size)
    pos_ok = (jnp.arange(records.shape[0]) // cfg.sigma) < n_live
    valid = valid & pos_ok
    records = records * valid[:, None].astype(records.dtype)
    return records, valid, {}


def plan(cfg: NGramConfig) -> plan_mod.JobPlan:
    """NAIVE as a :class:`JobPlan`: one job, exploded emit (the paper's
    worst-case record volume), whole-gram hash partitioning, exact count."""
    return plan_mod.JobPlan(
        name="naive",
        map=plan_mod.MapStage(_plan_emit),
        shuffle=plan_mod.ShuffleStage("gram"),
        sort=plan_mod.SortStage(),
        reduce=plan_mod.ReduceStage("exact"),
    )


def _distributed(tokens_p, cfg: NGramConfig, mesh, axis_name, capacity):
    n_parts = mesh.shape[axis_name]
    n_l = packing.n_lanes(cfg.sigma, cfg.vocab_size)

    def job(tok):
        tok = tok[0]
        if cfg.sigma > 1:
            perm = [(i, (i - 1) % n_parts) for i in range(n_parts)]
            halo = jax.lax.ppermute(tok[: cfg.sigma - 1], axis_name, perm)
            is_last = jax.lax.axis_index(axis_name) == n_parts - 1
            halo = jnp.where(is_last, jnp.zeros_like(halo), halo)
            tok_ext = jnp.concatenate([tok, halo])
        else:
            tok_ext = tok
        records, valid = _explode(tok_ext, cfg.sigma, cfg.vocab_size)
        pos_ok = (jnp.arange(records.shape[0]) // cfg.sigma) < tok.shape[0]
        valid = valid & pos_ok
        records = records * valid[:, None].astype(records.dtype)
        map_rec = jnp.sum(valid)
        key = gram_hash(records[:, :n_l])
        local, overflow = shf.shuffle(records, key, valid, axis_name=axis_name,
                                      n_parts=n_parts, capacity=capacity)
        terms, flags, counts = count_exact_grams(
            local, sigma=cfg.sigma, vocab_size=cfg.vocab_size)
        stats = jnp.stack([jax.lax.psum(map_rec, axis_name), overflow])
        return terms[None], flags[None], counts[None], stats[None]

    from jax.sharding import PartitionSpec as P
    fn = jax.jit(jax.shard_map(job, mesh=mesh, in_specs=(P(axis_name, None),),
                               out_specs=(P(axis_name),) * 4, check_vma=False))
    return fn(tokens_p)


def run(tokens, cfg: NGramConfig, mesh=None, axis_name: str = "data") -> NGramStats:
    if mesh is None or mesh.size == 1:
        from repro.pipeline.executor import run_plan
        return run_plan(jnp.asarray(tokens, jnp.int32), cfg, plan=plan(cfg))

    tokens = np.asarray(tokens, np.int32)
    n_parts = mesh.shape[axis_name]
    n = tokens.shape[0]
    n_local = -(-n // n_parts)
    tokens_p = shf.shard_rows(
        np.pad(tokens, (0, n_local * n_parts - n)).reshape(n_parts, n_local),
        mesh, axis_name)
    capacity = max(8, int(cfg.capacity_factor * n_local * cfg.sigma / n_parts) + 1)
    for attempt in range(6):
        terms, flags, counts, stats = _distributed(tokens_p, cfg, mesh, axis_name,
                                                   capacity)
        stats_np = np.asarray(stats)
        if int(stats_np[:, 1].max()) == 0:
            break
        capacity *= 2
    else:
        raise RuntimeError("naive shuffle overflow persisted")
    rec_bytes = packing.record_bytes(cfg.sigma, cfg.vocab_size)
    counters = {"map_records": int(stats_np[0, 0]),
                "shuffle_records": int(stats_np[0, 0]),
                "shuffle_bytes": int(stats_np[0, 0]) * rec_bytes,
                "jobs": 1, "overflow": 0, "capacity": capacity, "retries": attempt}
    terms, flags, counts = np.asarray(terms), np.asarray(flags), np.asarray(counts)
    out = None
    for p in range(n_parts):
        part = NGramStats.from_dense(terms[p], flags[p], counts[p], cfg.tau,
                                     counters if p == 0 else {})
        out = part if out is None else out.merged_with(part)
    return out
