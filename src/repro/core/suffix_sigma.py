"""SUFFIX-sigma (Algorithm 4 of the paper) as a single distributed JAX job.

Phases (one MapReduce job, like the paper):

  map      -- per token position emit the sigma-truncated suffix (bit-packed lanes)
              with weight 1; optional map-side combine merges equal suffixes.
  shuffle  -- partition by hash(first term) -> all_to_all (repro.mapreduce.shuffle).
  sort     -- lexicographic multi-key sort of the packed lanes.
  reduce   -- the paper's two-stack streaming aggregation, re-expressed data-parallel:
              LCP boundaries between adjacent sorted suffixes delimit the runs of every
              distinct prefix; run totals are segmented sums of the weights.  This is
              exact: the stack state at row i in Algorithm 4 is precisely the common
              prefix of rows i-1 and i, and a "pop + emit" happens exactly at an LCP
              drop -- i.e. at a run boundary.

The reducer never needs the reverse-lexicographic trick: that ordering exists so a
*streaming* reducer can emit early with O(sigma) state; the data-parallel reducer
instead processes a whole sorted block at once with O(block * sigma) VMEM state and
emits everything at the end of the block, which is the natural TPU formulation
(DESIGN.md SS2).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.mapreduce import pack as packing
from repro.mapreduce import shuffle
from repro.pipeline import plan as plan_mod
from repro.pipeline import stages
from .stats import NGramConfig, NGramStats

# --------------------------------------------------------------------------- map
@partial(jax.jit, static_argnames=("sigma",))
def suffix_windows(tokens: jax.Array, sigma: int) -> tuple[jax.Array, jax.Array]:
    """All sigma-truncated suffixes of a PAD-separated token stream.

    Returns (windows [N, sigma] int32 masked after the first PAD, valid [N] bool).
    """
    n = tokens.shape[0]
    padded = jnp.concatenate([tokens, jnp.zeros((sigma,), tokens.dtype)])
    # sigma static shifted slices, not an [N, sigma] index gather: on a TPU
    # the gather's temporaries were ~12x the whole fused wave program's rest
    w = jnp.stack([padded[j:j + n] for j in range(sigma)], axis=1)
    keep = jnp.cumprod((w != 0).astype(jnp.int32), axis=1)
    return (w * keep).astype(jnp.int32), tokens != 0


def make_records(tokens: jax.Array, *, sigma: int, vocab_size: int,
                 bucket_ids: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """Map emit: [N, W] uint32 records = packed lanes | weight | (bucket)."""
    windows, valid = suffix_windows(tokens, sigma)
    lanes = packing.pack_terms(windows, vocab_size=vocab_size)
    weight = valid.astype(jnp.uint32)
    cols = [lanes, weight[:, None]]
    if bucket_ids is not None:
        cols.append(bucket_ids.astype(jnp.uint32)[:, None])
    return jnp.concatenate(cols, axis=1), valid


# ------------------------------------------------------------------------ reduce
@partial(jax.jit, static_argnames=("sigma", "vocab_size", "n_buckets", "use_kernels"))
def reduce_block(records: jax.Array, *, sigma: int, vocab_size: int,
                 n_buckets: int = 0, use_kernels: bool = False):
    """Sort + count one reducer block (the fused form the distributed path
    calls; stage bodies live in ``pipeline.stages``).

    records: [N, W] = lanes | weight | (bucket).  Returns
    (terms [N, sigma], flags [N, sigma], counts [N, sigma] or [N, sigma, B]).
    """
    rec = stages.sort_stage(records, n_keys=packing.n_lanes(sigma, vocab_size))
    return stages.reduce_suffix(rec, sigma=sigma, vocab_size=vocab_size,
                                n_buckets=n_buckets, use_kernels=use_kernels)


# --------------------------------------------------------------------- job plan
def _plan_emit(tok_ext, aux_ext, n_live, cfg: NGramConfig, carry, k):
    """Map emit over one (possibly halo-extended) token window."""
    records, valid = make_records(tok_ext, sigma=cfg.sigma,
                                  vocab_size=cfg.lane_vocab,
                                  bucket_ids=aux_ext)
    pos_ok = jnp.arange(records.shape[0]) < n_live
    records = records * pos_ok[:, None].astype(records.dtype)
    valid = valid & pos_ok
    return records, valid, {}


def plan(cfg: NGramConfig) -> plan_mod.JobPlan:
    """SUFFIX-sigma as a :class:`JobPlan`: one job, suffix emit, optional
    combiner, lead-term partitioning, LCP-run reducer."""
    return plan_mod.JobPlan(
        name="suffix_sigma",
        map=plan_mod.MapStage(_plan_emit),
        combine=plan_mod.CombineStage(cfg.combine_route) if cfg.combine else None,
        shuffle=plan_mod.ShuffleStage("lead"),
        sort=plan_mod.SortStage(),
        reduce=plan_mod.ReduceStage("suffix"),
        lane_vocab=cfg.lane_vocab,
    )


# ------------------------------------------------------------------- distributed
def build_distributed_job(cfg: NGramConfig, mesh, axis_name: str, capacity: int,
                          has_bucket: bool = False):
    """Construct the (un-jitted) shard_map SUFFIX-sigma job for a mesh axis.

    Returned fn: (tokens [P, n_local], buckets [P, n_local] or dummy) ->
    (terms, flags, counts, stats) -- all sharded [P, ...].  Exposed separately so
    the dry-run can lower/compile the job on the production mesh (configs/paper.py).
    """
    n_parts = mesh.shape[axis_name]
    n_l = packing.n_lanes(cfg.sigma, cfg.lane_vocab)

    def job(tok, bkt):
        tok = tok[0]  # [n_local]
        # --- halo: suffixes near the shard end need the right neighbor's tokens.
        halo_src = tok[: cfg.sigma - 1] if cfg.sigma > 1 else tok[:0]
        if cfg.sigma > 1:
            perm = [(i, (i - 1) % n_parts) for i in range(n_parts)]
            halo = jax.lax.ppermute(halo_src, axis_name, perm)
            is_last = jax.lax.axis_index(axis_name) == n_parts - 1
            halo = jnp.where(is_last, jnp.zeros_like(halo), halo)
            tok_ext = jnp.concatenate([tok, halo])
        else:
            tok_ext = tok
        bucket = bkt[0] if has_bucket else None
        if bucket is not None and cfg.sigma > 1:
            bucket = jnp.concatenate([bucket, jnp.zeros((cfg.sigma - 1,), bucket.dtype)])
        records, valid = make_records(tok_ext, sigma=cfg.sigma,
                                      vocab_size=cfg.lane_vocab, bucket_ids=bucket)
        # halo positions belong to the neighbor: mask them out
        pos_ok = jnp.arange(records.shape[0]) < tok.shape[0]
        records = records * pos_ok[:, None].astype(records.dtype)
        valid = valid & pos_ok
        map_rec = jnp.sum(valid)
        if cfg.combine:
            records = stages.combine(records, n_l, has_bucket,
                                     route=cfg.combine_route,
                                     use_kernels=cfg.use_kernels)
        w = records[:, n_l]
        lead = packing.lead_term(records[:, 0], vocab_size=cfg.lane_vocab)
        local_rec, overflow = shuffle.shuffle(
            records, lead, w > 0, axis_name=axis_name, n_parts=n_parts,
            capacity=capacity)
        shuf_rec = jax.lax.psum(jnp.sum(local_rec[:, n_l] > 0), axis_name)
        terms, flags, counts = reduce_block(
            local_rec, sigma=cfg.sigma, vocab_size=cfg.lane_vocab,
            n_buckets=cfg.n_buckets, use_kernels=cfg.use_kernels)
        stats = jnp.stack([jax.lax.psum(map_rec, axis_name), shuf_rec, overflow])
        return terms[None], flags[None], counts[None], stats[None]

    from jax.sharding import PartitionSpec as P
    in_specs = (P(axis_name, None), P(axis_name, None) if has_bucket else P())
    out_specs = (P(axis_name), P(axis_name), P(axis_name), P(axis_name))
    return jax.shard_map(job, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def _distributed(tokens_sharded: jax.Array, cfg: NGramConfig, mesh, axis_name: str,
                 bucket_sharded, capacity: int):
    """Run one distributed SUFFIX-sigma job (tokens_sharded: [P, n_local])."""
    has_bucket = bucket_sharded is not None
    fn = jax.jit(build_distributed_job(cfg, mesh, axis_name, capacity, has_bucket))
    bkt_arg = bucket_sharded if has_bucket else jnp.zeros((1, 1), jnp.uint32)
    return fn(tokens_sharded, bkt_arg)


def run(tokens, cfg: NGramConfig, mesh=None, axis_name: str = "data",
        bucket_ids=None) -> NGramStats:
    """Run a SUFFIX-sigma job.  ``tokens``: 1-D int32, PAD(0)-separated documents."""
    if mesh is None or mesh.size == 1:
        from repro.pipeline.executor import run_plan
        bkt = None if bucket_ids is None else jnp.asarray(bucket_ids,
                                                          jnp.uint32)
        return run_plan(jnp.asarray(tokens, jnp.int32), cfg, bucket_ids=bkt,
                        plan=plan(cfg))

    # host arrays, placed shard by shard on the mesh
    tokens = np.asarray(tokens, np.int32)
    bkt = None if bucket_ids is None else np.asarray(bucket_ids, np.uint32)
    n_parts = mesh.shape[axis_name]
    n = tokens.shape[0]
    n_local = -(-n // n_parts)
    pad = n_local * n_parts - n
    tokens_p = shuffle.shard_rows(
        np.pad(tokens, (0, pad)).reshape(n_parts, n_local), mesh, axis_name)
    bkt_p = (shuffle.shard_rows(np.pad(bkt, (0, pad)).reshape(n_parts, n_local),
                                mesh, axis_name)
             if bkt is not None else None)

    capacity = max(8, int(cfg.capacity_factor * n_local / n_parts) + 1)
    for attempt in range(6):  # overflow -> double capacity and re-run (see shuffle.py)
        terms, flags, counts, stats = _distributed(
            tokens_p, cfg, mesh, axis_name, bkt_p, capacity)
        stats_np = np.asarray(stats)
        overflow = int(stats_np[:, 2].max())
        if overflow == 0:
            break
        capacity *= 2
    else:
        raise RuntimeError(f"shuffle overflow persisted at capacity {capacity}")

    rec_bytes = packing.record_bytes(cfg.sigma, cfg.lane_vocab,
                                     n_meta=1 if bkt is not None else 0)
    counters = {
        "map_records": int(stats_np[0, 0]),
        "shuffle_records": int(stats_np[0, 1]),
        "shuffle_bytes": int(stats_np[0, 1]) * rec_bytes,
        "jobs": 1,
        "overflow": overflow,
        "capacity": capacity,
        "retries": attempt,
    }
    out = None
    terms, flags, counts = np.asarray(terms), np.asarray(flags), np.asarray(counts)
    for p in range(n_parts):
        part = NGramStats.from_dense(terms[p], flags[p], counts[p], cfg.tau,
                                     counters if p == 0 else {})
        out = part if out is None else out.merged_with(part)
    return out
