"""shard_map and its replication-check switch (single source of truth).

Callers do:

    from repro.core.compat import shard_map, SHARD_MAP_CHECK_KW
    shard_map(f, mesh=..., in_specs=..., out_specs=..., **SHARD_MAP_CHECK_KW)
"""

from jax import shard_map  # noqa: F401

SHARD_MAP_CHECK_KW = {"check_vma": False}
