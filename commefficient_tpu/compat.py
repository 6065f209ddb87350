"""The one ``shard_map`` spelling every module uses: ``jax.shard_map`` with
positional ``mesh``/``in_specs``/``out_specs`` and the replication check
off by default (the round's collectives are explicit psums)."""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check_vma=False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


__all__ = ["shard_map"]
