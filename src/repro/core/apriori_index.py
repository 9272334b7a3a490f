"""APRIORI-INDEX (Algorithm 3): incremental inverted index with posting-list joins.

Phase 1 (k <= K): build positional occurrence information for frequent k-grams by
direct counting.  Phase 2 (k > K): a frequent (k)-gram occurrence at position p exists
iff frequent (k-1)-gram occurrences exist at p *and* p+1 -- which is exactly the
paper's Reducer-#2 join of the posting lists of the two constituent (k-1)-grams that
share a (k-2)-infix (position p lies in the joined list iff m occurs at p and n at
p+1).  SPADE-style, the join runs on the index, never rescanning the corpus.

TPU adaptation (DESIGN.md SS2): posting lists with positions become a boolean
occurrence mask over token positions (static shape), and the join becomes a shifted
AND of masks plus an exact re-count of the surviving grams.  Per-position run totals
are scattered back through the sort permutation (``count_exact_grams`` with
positions), giving each position the collection frequency of its gram -- the
"posting list with frequencies" of the paper.

Counters account posting-list volume the way the paper does: each iteration k > K
ships one record per surviving occurrence (O(cf(s)) bytes per frequent s).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.mapreduce import pack as packing
from repro.mapreduce import shuffle as shf
from repro.pipeline import plan as plan_mod
from .common import count_exact_grams, gram_hash, kgram_records
from .stats import NGramConfig, NGramStats, add_counters


def _join_mask(cfg: NGramConfig, k: int, occ):
    """Phase-2 posting-list join: a k-gram occurs at p iff frequent
    (k-1)-grams occur at p and p+1; phase 1 (k <= K) has no precondition."""
    if k <= min(cfg.apriori_index_k, cfg.sigma) or occ is None:
        return None
    nxt = jnp.concatenate([occ[1:], jnp.zeros((1,), bool)])
    return occ & nxt


def _plan_emit(tok_ext, aux_ext, n_live, cfg: NGramConfig, carry, k):
    """Round-k map emit: k-grams at positions allowed by the occurrence mask.

    ``window_valid`` (the *unmasked* join-passing positions over the whole
    extended window, halo included) rides along for the wave-mode carry.
    """
    mask = _join_mask(cfg, k, carry)
    records, valid = kgram_records(tok_ext, k, cfg.sigma, cfg.vocab_size,
                                   weight_mask=mask, with_positions=True)
    pos_ok = jnp.arange(records.shape[0]) < n_live
    live_valid = valid & pos_ok
    # mask lanes + weight but KEEP the position meta lane: zeroed positions
    # would collide every invalid row onto index 0 in the reducer's
    # totals-at-pos scatter, whose duplicate-index winner is unspecified
    records = jnp.concatenate(
        [records[:, :-1] * live_valid[:, None].astype(records.dtype),
         records[:, -1:]], axis=1)
    return records, live_valid, {"window_valid": valid}


def _update_carry(cfg: NGramConfig, tau_eff, k, tok_ext, stats_k,
                  reduce_extras, emit_extras, carry):
    """Occurrence mask of frequent k-grams for the next round's join.

    ``tau_eff == 1`` is the wave regime: "frequent" means "occurs", which the
    emit already knows for every window position including the halo --
    counts-based occupancy would be blind to halo positions and prune real
    occurrences at wave boundaries.  Otherwise the paper's rule: positions
    whose gram's collection frequency reaches tau (the per-position run
    totals shipped back through the sort permutation).
    """
    if tau_eff == 1:
        return emit_extras["window_valid"]
    return jnp.asarray(np.asarray(reduce_extras["totals_pos"]) >= tau_eff)


def plan(cfg: NGramConfig) -> plan_mod.JobPlan:
    """APRIORI-INDEX as a :class:`JobPlan`: sigma chained jobs, occurrence-mask
    carry (the posting-list join), exact counting with position payloads."""
    return plan_mod.JobPlan(
        name="apriori_index",
        map=plan_mod.MapStage(_plan_emit, n_meta=1),
        shuffle=plan_mod.ShuffleStage("gram"),
        sort=plan_mod.SortStage(),
        reduce=plan_mod.ReduceStage("exact", with_positions=True),
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
    """Distributed variant: positions sharded contiguously over the mesh axis, so the
    p+1 join is local except for a single boundary element exchanged by ppermute; the
    gram re-count shuffles by gram hash like the other methods."""
    n_parts = mesh.shape[axis_name]
    n = tokens.shape[0]
    n_local = -(-n // n_parts)
    tokens_p = shf.shard_rows(
        np.pad(tokens, (0, n_local * n_parts - n)).reshape(n_parts, n_local),
        mesh, axis_name)
    n_l = packing.n_lanes(cfg.sigma, cfg.vocab_size)
    rec_width = packing.record_bytes(cfg.sigma, cfg.vocab_size, n_meta=1)

    def stage_fn(k, capacity, joined):
        def job(tok, occ):
            tok, occ = tok[0], occ[0]
            perm = [(i, (i - 1) % n_parts) for i in range(n_parts)]
            is_last = jax.lax.axis_index(axis_name) == n_parts - 1
            if cfg.sigma > 1:
                halo = jax.lax.ppermute(tok[: cfg.sigma - 1], axis_name, perm)
                halo = jnp.where(is_last, jnp.zeros_like(halo), halo)
                tok_ext = jnp.concatenate([tok, halo])
            else:
                tok_ext = tok
            if joined:
                occ_next = jax.lax.ppermute(occ[:1], axis_name, perm)
                occ_next = jnp.where(is_last, jnp.zeros_like(occ_next), occ_next)
                nxt = jnp.concatenate([occ[1:], occ_next])
                mask = occ & nxt
            else:
                mask = None
            records, valid = kgram_records(tok_ext, k, cfg.sigma, cfg.vocab_size,
                                           weight_mask=(None if mask is None else
                                                        jnp.pad(mask, (0, cfg.sigma - 1))
                                                        if cfg.sigma > 1 else mask),
                                           with_positions=True)
            pos_ok = jnp.arange(records.shape[0]) < tok.shape[0]
            valid = valid & pos_ok
            records = records * valid[:, None].astype(records.dtype)
            n_rec = jnp.sum(valid)
            # re-count by gram: shuffle occurrences to the gram's reducer, count,
            # then ship totals back to the home shard of each position.
            key = gram_hash(records[:, :n_l])
            local, overflow = shf.shuffle(records, key, valid, axis_name=axis_name,
                                          n_parts=n_parts, capacity=capacity)
            terms, flags, counts, totals_pos_global = count_exact_grams(
                local, sigma=cfg.sigma, vocab_size=cfg.vocab_size,
                with_positions=True)
            # totals_pos_global is indexed by *global* position but lives on the
            # reducer shard; scatter-add back: every shard contributes its counted
            # occurrences, summed across shards via psum of a sharded one-hot write.
            my_totals = jnp.zeros((n_parts * n_local,), jnp.int32)
            pos = local[:, n_l + 1].astype(jnp.int32)
            w = (local[:, n_l] > 0)
            seg_tot = _row_totals(local, n_l)
            my_totals = my_totals.at[jnp.where(w, pos, n_parts * n_local)].set(
                seg_tot, mode="drop")
            my_totals = jax.lax.psum(my_totals, axis_name)
            shard = jax.lax.axis_index(axis_name)
            occ_out = jax.lax.dynamic_slice(my_totals, (shard * n_local,), (n_local,))
            stats = jnp.stack([jax.lax.psum(n_rec, axis_name), overflow])
            return (terms[None], flags[None], counts[None],
                    (occ_out >= cfg.tau)[None], stats[None])
        return job

    def _row_totals(local, n_l):
        # run totals aligned to `local` row order (recomputed from a sort -- cheap
        # next to the shuffle), used to ship per-position counts home.
        from repro.mapreduce import sort as srt
        rec = srt.sort_records(local, n_keys=n_l)
        lanes = rec[:, :n_l]
        first = jnp.any(lanes != jnp.roll(lanes, 1, axis=0), axis=1).at[0].set(True)
        seg = jnp.maximum(jnp.cumsum(first.astype(jnp.int32)) - 1, 0)
        totals = jax.ops.segment_sum(rec[:, n_l].astype(jnp.int32), seg,
                                     num_segments=rec.shape[0])[seg]
        pos_sorted = rec[:, n_l + 1].astype(jnp.int32)
        w_sorted = rec[:, n_l] > 0
        buf = jnp.zeros((n_parts * n_local,), jnp.int32)
        buf = buf.at[jnp.where(w_sorted, pos_sorted, n_parts * n_local)].set(
            totals, mode="drop")
        return buf[local[:, n_l + 1].astype(jnp.int32)]

    from jax.sharding import PartitionSpec as P
    counters: dict[str, float] = {"jobs": 0, "map_records": 0, "shuffle_records": 0,
                                  "shuffle_bytes": 0, "overflow": 0}
    out = None
    K = min(cfg.apriori_index_k, cfg.sigma)
    occ_p = shf.shard_rows(np.zeros((n_parts, n_local), bool), mesh, axis_name)
    for k in range(1, cfg.sigma + 1):
        capacity = max(8, int(cfg.capacity_factor * n_local / n_parts) + 1)
        for attempt in range(6):
            fn = jax.jit(jax.shard_map(
                stage_fn(k, capacity, joined=k > K), mesh=mesh,
                in_specs=(P(axis_name, None), P(axis_name, None)),
                out_specs=(P(axis_name),) * 5, check_vma=False))
            terms, flags, counts, occ_new, stats = fn(tokens_p, occ_p)
            stats_np = np.asarray(stats)
            if int(stats_np[:, 1].max()) == 0:
                break
            capacity *= 2
        else:
            raise RuntimeError("apriori_index shuffle overflow persisted")
        n_rec = int(stats_np[0, 0])
        add_counters(counters, jobs=1, map_records=n_rec, shuffle_records=n_rec,
                     shuffle_bytes=n_rec * rec_width)
        terms, flags, counts = np.asarray(terms), np.asarray(flags), np.asarray(counts)
        st = None
        for p in range(n_parts):
            part = NGramStats.from_dense(terms[p], flags[p], counts[p], cfg.tau)
            st = part if st is None else st.merged_with(part)
        out = st if out is None else out.merged_with(st)
        occ_p = occ_new
        if len(st) == 0:
            break
    out.counters = counters
    return out
