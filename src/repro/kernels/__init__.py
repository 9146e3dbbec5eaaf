"""Pallas TPU kernels for the paper's compute hot-spots.

range_query/  — batched AABB range probe over packed R-tree leaves
                (the RangeReach online hot path).
bitset_mm/    — packed uint32 boolean OR-AND matmul (the Alg. 1 closure
                build step as a semiring matmul; + MXU variant in ops).
forest_build/ — segmented-MBR reduction (the R-tree bulk-load level
                step; also builds the query engines' tile pyramids).

Each: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd public
wrapper), ref.py (pure-jnp oracle). Validated vs ref in interpret mode;
see tests/test_kernels.py for the shape sweeps.
"""
