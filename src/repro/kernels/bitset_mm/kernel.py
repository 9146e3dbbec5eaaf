"""Pallas TPU kernel: packed boolean OR-AND matmul (bitset closure step).

The reverse-topological set-merge of paper Alg. 1 is, in dense form, the
fixpoint  R <- OWN | A.R  over the boolean semiring (OR, AND), where A is
the condensation adjacency and R the reachable-set matrix.  Packing 32
spatial columns per uint32 word makes one VPU op process 32 set-union
lanes at once — this kernel computes one semiring matmul

    out[i, w] = OR_j ( A[i, j] AND R[j, w] )

with A packed along j (``(d, Wd)`` words) and R packed along columns
(``(dj, W)`` words).  Blocking: one word-column of A per grid step (32
j's), unrolled as 32 masked OR accumulations over a (32, TW) R tile held
in VMEM.  The out tile is revisited across the reduction dimension.

The MXU alternative (unpack bits to bf16 and use a real matmul, then
re-threshold) is provided in ops.py as ``bitset_mm_mxu`` — see
EXPERIMENTS.md §Perf for the crossover analysis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


TI = 8      # rows of A / out per tile (sublanes)
TW = 128    # words of R / out per tile (lanes)


def _bitset_mm_kernel(a_ref, r_ref, o_ref):
    jw = pl.program_id(2)

    @pl.when(jw == 0)
    def _init():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    a = a_ref[...]                  # (TI, 1) uint32 — one word-column of A
    r = r_ref[...]                  # (32, TW) uint32
    acc = o_ref[...]                # (TI, TW)
    for k in range(32):
        bit = ((a >> jnp.uint32(k)) & jnp.uint32(1)) != 0     # (TI, 1)
        acc = acc | jnp.where(bit, r[k:k + 1, :], jnp.uint32(0))
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("interpret", "ti", "tw"))
def bitset_mm_pallas(
    a_bits: jax.Array,   # (d, Wd) uint32; d % ti == 0
    r_bits: jax.Array,   # (Wd*32, W) uint32; W % tw == 0
    *,
    interpret: bool = False,
    ti: int = TI,
    tw: int = TW,
) -> jax.Array:
    d, Wd = a_bits.shape
    dj, W = r_bits.shape
    assert dj == Wd * 32, (dj, Wd)
    assert d % ti == 0 and W % tw == 0, (d, W)
    grid = (d // ti, W // tw, Wd)
    # A enters word-column-major as (Wd, d, 1): a step's (ti, 1) block
    # spans the full trailing dim, which the TPU block rule accepts (a
    # (ti, 1) block of the (d, Wd) matrix would not)
    return pl.pallas_call(
        _bitset_mm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, ti, 1), lambda i, w, jw: (jw, i, 0)),
            pl.BlockSpec((32, tw), lambda i, w, jw: (jw, w)),
        ],
        out_specs=pl.BlockSpec((ti, tw), lambda i, w, jw: (i, w)),
        out_shape=jax.ShapeDtypeStruct((d, W), jnp.uint32),
        interpret=interpret,
    )(a_bits.T[:, :, None], r_bits)
