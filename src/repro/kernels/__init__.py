"""Pallas TPU kernel for the system's compute hot spot: the ELL block
histogram.

The kernel ships as ell_histogram.py (pl.pallas_call + BlockSpec), a jit'd
wrapper in ops.py, and a pure-jnp oracle in ref.py.  CPU tests run the
kernel in interpret mode; ops.py compiles it where the default backend is
a TPU.  Interpret mode cannot see what the TPU compiler refuses, so the
kernel is also compiled for a described v5e (tests/test_tpu_compile.py)
and run on the chip by chip_smoke.py.

`block_histogram` resolves lazily (PEP 562, same scheme as
`repro.distributed`): `repro.core` reaches into this package for its
multilevel engines, and a plain `import repro.kernels` must not drag the
jax stack into the pure-host partitioning path (RPR001's contract — the
jax import happens when the op is actually requested).
"""

__all__ = ["block_histogram"]


def __getattr__(name: str):
    if name != "block_histogram":
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    from repro.kernels.ops import block_histogram

    globals()[name] = block_histogram
    return block_histogram


def __dir__():
    return sorted(set(globals()) | set(__all__))
