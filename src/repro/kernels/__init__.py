"""Pallas TPU kernels for the system's compute hot spots.

Each kernel ships as <name>.py (pl.pallas_call + BlockSpec), a jit'd wrapper
in ops.py, and a pure-jnp oracle in ref.py.  CPU tests run the kernels in
interpret mode; ops.py compiles them where the default backend is a TPU.
Interpret mode cannot see what the TPU compiler refuses, so the histogram
and Fennel kernels are also compiled for a described v5e
(tests/test_tpu_compile.py) and run on the chip by chip_smoke.py.

Public ops resolve lazily (PEP 562, same scheme as `repro.distributed`):
`repro.core` reaches into this package for its multilevel engines, and a
plain `import repro.kernels` must not drag the jax stack into the pure-host
partitioning path (RPR001's contract — the jax import happens when an op is
actually requested).
"""

_LAZY = {
    "block_histogram": "ops",
    "fennel_choose_batch": "ops",
    "embedding_bag": "ops",
    "swa_attention_decode": "ops",
}

__all__ = list(_LAZY)


def __getattr__(name: str):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    module = importlib.import_module(f"{__name__}.{mod}")
    # bind every public name of this backing module at once: importing ops
    # binds the *submodule* `embedding_bag` as a package attribute (normal
    # submodule-import semantics), which would otherwise shadow the lazy op
    # of the same name on the next lookup
    for attr, m in _LAZY.items():
        if m == mod:
            globals()[attr] = getattr(module, attr)
    return globals()[name]


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
