"""Distributed runtime: shard-parallel partitioning and GNN placement.

Submodules are imported lazily (PEP 562): `shard_driver` is pure
numpy + threads and must stay importable — and fork-safe for its process
backend — without importing anything else of this package.
"""

_LAZY = {
    # shard-parallel partitioning (numpy + threads, fork-safe)
    "ShardPool": "shard_driver",
    "SharedLoads": "shard_driver",
    "ShardWorkerError": "shard_driver",
    "shard_partition": "shard_driver",
    "SHARD_BACKENDS": "shard_driver",
}

__all__ = list(_LAZY)


def __getattr__(name: str):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    value = getattr(importlib.import_module(f"{__name__}.{mod}"), name)
    globals()[name] = value
    return value


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
