"""Device-resident n-gram index + batched query serving.

The read side of the system: ``build`` freezes a finished job's ``NGramStats``
into a sorted packed-lane artifact (``IndexSegment`` -- the immutable unit of
composition), ``compress`` re-encodes it losslessly (front-coded blocks +
Elias-Fano monotone structures, ~3x smaller), ``merge`` composes sorted
segments without re-running the job and keeps generations of them fresh under
streaming ingest (``GenerationalIndex``, LSM-style size-tiered compaction),
``query`` answers batched point-count and top-k-continuation queries against
any layout or a whole generation stack, and ``serve`` shards everything over a
mesh with the job shuffle's own hash partitioner (shards align with reducer
outputs; cross-shard and cross-segment folds run on the host).
"""
from . import build, compress, merge, query, serve
from .build import (IndexSegment, NGramIndex, build_index, index_from_segment,
                    segment_from_stats)
from .compress import (CompressedNGramIndex, EliasFano, build_compressed_index,
                       compress_index, decode_segment)
from .merge import (GenerationalIndex, generational_from_stats,
                    merge_indexes, merge_segments, segment_to_stats,
                    stats_union)
from .query import continuations, lookup
from .serve import (ShardedGenerationalIndex, ShardedNGramIndex,
                    build_sharded_index, empty_prefix_continuations,
                    make_server, shard_generational)
from .serve import serve as serve_queries

__all__ = ["build", "compress", "merge", "query", "serve",
           "IndexSegment", "NGramIndex", "build_index", "index_from_segment",
           "segment_from_stats",
           "CompressedNGramIndex", "EliasFano", "build_compressed_index",
           "compress_index", "decode_segment",
           "GenerationalIndex", "generational_from_stats",
           "merge_indexes", "merge_segments", "segment_to_stats",
           "stats_union",
           "lookup", "continuations",
           "ShardedGenerationalIndex", "ShardedNGramIndex",
           "build_sharded_index", "empty_prefix_continuations", "make_server",
           "shard_generational", "serve_queries"]
