"""APRIORI-SCAN (Algorithm 2): one distributed scan of the corpus per gram length.

The k-th job emits only those k-grams whose two constituent (k-1)-grams were output
(frequent) by job k-1 -- candidate pruning via the APRIORI principle.  The paper keeps
the previous job's output in a per-node dictionary (distributed cache / BerkeleyDB);
our TPU analogue is a sorted uint32 hash array broadcast to all devices with binary
search lookups (``common.membership_hashes``).  Hash collisions can only admit extra
candidates, which the exact re-count of job k then filters -- output equality with the
oracle is preserved, only pruning power degrades (negligibly at 2^-32).

Termination matches the paper: after sigma jobs or when a job produces no output.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.mapreduce import pack as packing
from repro.mapreduce import shuffle as shf
from repro.pipeline import plan as plan_mod
from .common import count_exact_grams, gram_hash, kgram_records, member, membership_hashes
from .stats import NGramConfig, NGramStats, add_counters
from .suffix_sigma import suffix_windows


def _candidates(tokens: jax.Array, k: int, cfg: NGramConfig,
                freq_hashes: jax.Array | None):
    """Candidate k-gram records at every position (pruned by the (k-1) dictionary)."""
    sigma, vocab = cfg.sigma, cfg.vocab_size
    if k == 1 or freq_hashes is None:
        return kgram_records(tokens, k, sigma, vocab)
    windows, _ = suffix_windows(tokens, sigma)
    km1 = jnp.arange(sigma) < (k - 1)
    prefix = windows * km1[None, :].astype(windows.dtype)                 # d[b..b+k-2]
    suffix_w = jnp.roll(windows, -1, axis=0) * km1[None, :].astype(windows.dtype)
    pref_ok = member(freq_hashes,
                     gram_hash(packing.pack_terms(prefix, vocab_size=vocab)))
    suff_ok = member(freq_hashes,
                     gram_hash(packing.pack_terms(suffix_w, vocab_size=vocab)))
    mask = pref_ok & suff_ok
    return kgram_records(tokens, k, sigma, vocab, weight_mask=mask)


def _plan_emit(tok_ext, aux_ext, n_live, cfg: NGramConfig, carry, k):
    """Round-k map emit: candidate k-grams pruned by the (k-1) dictionary.

    The pre-live-mask records/valid (whole window, halo included) ride along
    in ``emit_extras`` for the wave-mode carry, which needs exactly them.
    """
    records, valid = _candidates(tok_ext, k, cfg, carry)
    pos_ok = jnp.arange(records.shape[0]) < n_live
    live_valid = valid & pos_ok
    live_records = records * live_valid[:, None].astype(records.dtype)
    return live_records, live_valid, {"window_records": records,
                                      "window_valid": valid}


def _update_carry(cfg: NGramConfig, tau_eff, k, tok_ext, stats_k,
                  reduce_extras, emit_extras, carry):
    """Next round's dictionary (the Hadoop distributed-cache analogue).

    ``tau_eff == 1`` is the wave regime: every k-gram of the window (halo
    included) is "frequent", and the dictionary must cover the halo or the
    candidate test at wave-boundary positions would prune real occurrences --
    so it is built from the emit's own window records (at tau=1 the candidate
    mask admits every valid position, so they are exactly the window's
    k-grams; no second emit).  Otherwise (the monolithic job) it is the
    hashes of this round's frequent output, as in the paper.
    """
    if tau_eff == 1:
        n_l = packing.n_lanes(cfg.sigma, cfg.vocab_size)
        return membership_hashes(emit_extras["window_records"][:, :n_l],
                                 emit_extras["window_valid"])
    freq_lane = packing.pack_terms(jnp.asarray(stats_k.grams),
                                   vocab_size=cfg.vocab_size)
    return membership_hashes(freq_lane, jnp.asarray(stats_k.lengths == k))


def plan(cfg: NGramConfig) -> plan_mod.JobPlan:
    """APRIORI-SCAN as a :class:`JobPlan`: sigma chained jobs, candidate emit
    pruned by the previous round's dictionary carry, whole-gram counting."""
    return plan_mod.JobPlan(
        name="apriori_scan",
        map=plan_mod.MapStage(_plan_emit),
        shuffle=plan_mod.ShuffleStage("gram"),
        sort=plan_mod.SortStage(),
        reduce=plan_mod.ReduceStage("exact"),
        rounds=cfg.sigma,
        stop_on_empty=True,
        update_carry=_update_carry,
    )


def run(tokens, cfg: NGramConfig, mesh=None, axis_name: str = "data") -> NGramStats:
    if mesh is not None and mesh.size > 1:
        return _run_distributed(np.asarray(tokens, np.int32), cfg, mesh,
                                axis_name)
    from repro.pipeline.executor import run_plan
    return run_plan(jnp.asarray(tokens, jnp.int32), cfg, plan=plan(cfg))


def _run_distributed(tokens, cfg: NGramConfig, mesh, axis_name) -> NGramStats:
    n_parts = mesh.shape[axis_name]
    n = tokens.shape[0]
    n_local = -(-n // n_parts)
    tokens_p = shf.shard_rows(
        np.pad(tokens, (0, n_local * n_parts - n)).reshape(n_parts, n_local),
        mesh, axis_name)
    n_l = packing.n_lanes(cfg.sigma, cfg.vocab_size)
    rec_width = packing.record_bytes(cfg.sigma, cfg.vocab_size)

    def stage_fn(k, capacity, dict_size):
        def job(tok, freq):
            tok = tok[0]
            freq = freq if dict_size else None  # replicated dictionary (dist. cache)
            if cfg.sigma > 1:
                perm = [(i, (i - 1) % n_parts) for i in range(n_parts)]
                halo = jax.lax.ppermute(tok[: cfg.sigma - 1], axis_name, perm)
                is_last = jax.lax.axis_index(axis_name) == n_parts - 1
                halo = jnp.where(is_last, jnp.zeros_like(halo), halo)
                tok_ext = jnp.concatenate([tok, halo])
            else:
                tok_ext = tok
            records, valid = _candidates(tok_ext, k, cfg, freq)
            pos_ok = jnp.arange(records.shape[0]) < tok.shape[0]
            valid = valid & pos_ok
            records = records * valid[:, None].astype(records.dtype)
            n_cand = jnp.sum(valid)
            key = gram_hash(records[:, :n_l])
            local, overflow = shf.shuffle(records, key, valid, axis_name=axis_name,
                                          n_parts=n_parts, capacity=capacity)
            terms, flags, counts = count_exact_grams(
                local, sigma=cfg.sigma, vocab_size=cfg.vocab_size)
            stats = jnp.stack([jax.lax.psum(n_cand, axis_name), overflow])
            return terms[None], flags[None], counts[None], stats[None]
        return job

    from jax.sharding import PartitionSpec as P
    counters: dict[str, float] = {"jobs": 0, "map_records": 0, "shuffle_records": 0,
                                  "shuffle_bytes": 0, "overflow": 0}
    out = None
    freq_hashes_host = None
    for k in range(1, cfg.sigma + 1):
        capacity = max(8, int(cfg.capacity_factor * n_local / n_parts) + 1)
        dict_size = 0 if freq_hashes_host is None else freq_hashes_host.shape[0]
        freq_arg = (jnp.zeros((1,), jnp.uint32) if dict_size == 0
                    else jnp.asarray(freq_hashes_host))
        for attempt in range(6):
            job = stage_fn(k, capacity, dict_size)
            fn = jax.jit(jax.shard_map(
                job, mesh=mesh, in_specs=(P(axis_name, None), P()),
                out_specs=(P(axis_name),) * 4, check_vma=False))
            terms, flags, counts, stats = fn(tokens_p, freq_arg)
            stats_np = np.asarray(stats)
            if int(stats_np[:, 1].max()) == 0:
                break
            capacity *= 2
        else:
            raise RuntimeError("apriori_scan shuffle overflow persisted")
        n_cand = int(stats_np[0, 0])
        add_counters(counters, jobs=1, map_records=n_cand, shuffle_records=n_cand,
                     shuffle_bytes=n_cand * rec_width)
        terms, flags, counts = np.asarray(terms), np.asarray(flags), np.asarray(counts)
        stage = None
        for p in range(n_parts):
            part = NGramStats.from_dense(terms[p], flags[p], counts[p], cfg.tau)
            stage = part if stage is None else stage.merged_with(part)
        out = stage if out is None else out.merged_with(stage)
        if len(stage) == 0:
            break
        freq_lane = packing.pack_terms(jnp.asarray(stage.grams),
                                       vocab_size=cfg.vocab_size)
        # dictionary replicated to every node -- Hadoop distributed-cache analogue
        freq_hashes_host = np.asarray(
            membership_hashes(freq_lane, jnp.asarray(stage.lengths == k)))
    out.counters = counters
    return out
