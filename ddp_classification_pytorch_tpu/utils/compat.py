"""shard_map with replication checking off — the one spelling every
hand-written collective in the repo goes through."""

from __future__ import annotations

import jax


def shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
