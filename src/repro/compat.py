"""``jax.shard_map`` with the replication check off by default.

The serving programs return values they reduce themselves
(``psum``/``pmax``), so the static varying-manual-axes check
(``check_vma``) is opt-in.
"""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with ``check_vma=check`` (default off)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
