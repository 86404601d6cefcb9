"""Compute/communication overlap primitives.

`collective_matmul_allgather` is the decomposed collective matmul
[Wang et al., "Overlap communication with dependent computation", ASPLOS'23]:
instead of all-gather(x) -> matmul (serializing a full ICI transfer before
any MXU work), the gather is unrolled into a ring of collective_permutes,
each overlapped with the matmul of the shard that is already resident.
XLA's latency-hiding scheduler can then run step i's permute concurrently
with step i-1's partial matmul. Used by the §Perf hillclimb for TP-bound
layers; numerics are exactly the all-gather matmul (same summation order
per output tile).
"""
from __future__ import annotations

import jax  # repro: noqa RPR001 -- jax-resident module behind PEP-562-lazy distributed/__init__
import jax.numpy as jnp  # repro: noqa RPR001 -- jax-resident module


def collective_matmul_allgather(x_local, w, axis_name: str):
    """Compute all_gather(x, axis) @ w without a monolithic all-gather.

    x_local: this shard's rows (B_local, K); w: (K, N) replicated (or
    TP-sharded on N outside). Returns (B_local * n_shards, N) — the same
    as jnp.concatenate(all_gather(x)) @ w.

    Ring schedule: at step s, multiply the chunk received s hops ago while
    forwarding the buffer to the next neighbor.
    """
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b_local = x_local.shape[0]

    def step(carry, s):
        buf, out = carry
        # the chunk currently held originated at (my + s) % n
        src = (my + s) % n
        part = buf @ w
        out = jax.lax.dynamic_update_slice(out, part, (src * b_local, 0))
        # forward the buffer around the ring (skip after the last use)
        buf = jax.lax.ppermute(
            buf, axis_name, [(i, (i - 1) % n) for i in range(n)]
        )
        return (buf, out), None

    out0 = jnp.zeros((b_local * n, w.shape[1]), x_local.dtype)
    # mark the accumulator as device-varying so the scan carry types match
    # (its contents depend on axis_index from step 0 onward)
    out0 = jax.lax.pcast(out0, axis_name, to="varying")
    (buf, out), _ = jax.lax.scan(step, (x_local, out0), jnp.arange(n))
    return out


def allgather_matmul_reference(x_local, w, axis_name: str):
    """The baseline the decomposition must match numerically."""
    xs = jax.lax.all_gather(x_local, axis_name)  # (n, B_local, K)
    x_full = xs.reshape(-1, x_local.shape[-1])
    return x_full @ w
