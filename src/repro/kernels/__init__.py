"""Pallas TPU kernels for the SUFFIX-sigma hot spots (see each module's
docstring for the VMEM tiling rationale):

  lcp_boundary   -- reducer inner loop (LCP + per-length boundary flags)
  suffix_pack    -- map emit (windowed gather + bit pack, fused)
  hash_partition -- shuffle partitioner (hash + histogram, fused)
  hash_combine   -- sort-free map-side combiner (block-local hash slots)
  bsearch        -- index serving inner loop (batched lexicographic bounds)
  block_decode   -- compressed-index in-block decode + rank
  block_expand   -- compressed-index batched block decode
  merge_path     -- stable two-way merge of sorted segments (no caller outside tests)

``ops`` holds the public wrappers (compiled on a TPU, interpreted elsewhere),
``ref`` the pure-jnp oracles.  The package imports none of them eagerly, so
``repro.kernels.<name>`` is always the module of that name.
"""
