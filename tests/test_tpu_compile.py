"""Ahead-of-time compiles for a described TPU v5e: no chip needed.

Interpret mode hides what the TPU compiler refuses (a dynamic slice of a
loaded value, an int64 index inside a kernel, a tile over the VMEM
budget), and CPU runs never see it.  These tests compile the Pallas kernels
and the level-0 refine stage for a v5e at the shapes the main path admits
at paper batch size: 65,536 rows (the pow2 bucket of a δ = 32,768 batch
plus its k = 32 aux nodes), ELL widths up to the 2^24 tile-volume cap, k =
32 (128 label columns) for refine and a K-tiled cluster-label domain.  The
engine runs under x64, so every kernel compiles with and without it.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and a test worker that
collected different tests than its siblings would stop the whole run.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

ROWS = 65536    # bucket of a 32,768-node batch plus 32 aux nodes
E_PAD = 1 << 20  # edge bucket of such a batch model graph
K = 32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_cache():
    """A described chip's programs are written to the persistent cache but
    can never be read back (the next read warns): keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("x64", [False, True])
@pytest.mark.parametrize("rows,width,k", [
    (ROWS, 8, K),      # narrowest ELL tile
    (ROWS, 256, K),    # widest tile under the volume cap at this row count
    (4096, 64, 4096),  # K-tiled cluster labels (label id = node id)
])
def test_ell_histogram_compiles(one_chip, no_cache, rows, width, k, x64):
    with jax.enable_x64(x64):
        compiled = jax.jit(
            lambda b, w: ops.block_histogram(b, w, k, use_kernel=True,
                                             interpret=False)
        ).lower(_spec(one_chip, (rows, width), jnp.int32),
                _spec(one_chip, (rows, width), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode,width,e_pad", [
    pytest.param("ell", 256, E_PAD, id="ell-256"),
    # mesh batches, 2^19 edge slots: a structured grid's (degree at most
    # 6) and a Delaunay mesh's (free rows up to 32 wide)
    pytest.param("ell", 8, E_PAD // 2, id="ell-8-mesh"),
    pytest.param("ell", 32, E_PAD // 2, id="ell-32-mesh"),
    pytest.param("dense", 8, E_PAD, id="dense-8"),
])
def test_level0_refine_compiles(one_chip, no_cache, monkeypatch, mode, width, e_pad):
    """The refine stage of a δ = 32,768 batch at level 0: the ELL mode runs
    the Pallas histogram inside the stage, dense the scatter twin; both
    with the sort-free capacity acceptance over the k + 1 block slots, all
    under x64."""
    import repro.core.multilevel_jax as mlj

    # the engine turns the kernels on from the backend it runs on; the
    # described chip is not that backend, so say it here
    monkeypatch.setattr(ops, "USE_KERNELS_DEFAULT", True)
    with jax.enable_x64(True):
        def s(shape, dt):
            return _spec(one_chip, shape, dt)

        i32, f64 = jnp.int32, jnp.float64
        compiled = jax.jit(
            mlj._lp_refine, static_argnames=("rounds", "mode"),
        ).lower(
            s((e_pad,), i32), s((e_pad,), i32), s((e_pad,), f64),
            s((ROWS, width), i32), s((ROWS, width), f64),
            s((ROWS,), f64), s((ROWS,), i32), s((), i32),
            s((ROWS,), i32), s((K,), f64), s((), f64),
            rounds=3, mode=mode,
        ).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (mode == "ell")


def _cluster_stage(name, s):
    """(function, argument specs, static kwargs) of one stage of a level-0
    clustering round at ROWS nodes and E_PAD edge slots."""
    import repro.core.multilevel_jax as mlj

    i32, u32, f64 = jnp.int32, jnp.uint32, jnp.float64
    rows = [s((ROWS,), dt) for dt in (i32, u32, f64)]
    r_i32, r_u32, r_f64 = rows
    edges = (s((E_PAD,), i32), s((E_PAD,), i32), s((E_PAD,), f64),
             s((1, 8), i32), s((1, 8), f64), r_f64, r_i32, s((), i32),
             s((), f64))
    if name == "round0":
        return mlj._lp_cluster_round, (*edges, None, None, None, None), {
            "mode": "sort"}
    if name == "round-sort":
        return mlj._lp_cluster_round, (
            *edges, r_i32, r_f64, s((E_PAD,), u32), s((E_PAD,), i32)), {
            "mode": "sort"}
    return {
        "sort": (mlj._sort, (r_u32,)),
        "hi_key": (mlj._accept_hi_key, (r_u32, r_i32, r_u32)),
        "gain_key": (mlj._accept_gain_key, (r_u32, r_i32, r_i32, r_i32, r_i32)),
        "target_key": (mlj._accept_target_key, (r_u32, r_i32, r_i32, r_i32)),
        "id_key": (mlj._accept_id_key, (r_u32, r_i32, r_i32)),
        "cluster_accept": (mlj._cluster_accept, (
            r_i32, r_i32, r_i32, r_f64, r_i32, r_f64, s((), f64))),
    }[name] + ({},)


@pytest.mark.parametrize("stage", [
    "round0", "round-sort", "sort", "hi_key", "gain_key", "target_key",
    "id_key", "cluster_accept",
])
def test_level0_cluster_stages_compile(one_chip, no_cache, stage):
    """The clustering round of a δ = 32,768 batch at level 0, stage by
    stage, under x64: the aggregation program (round 0's all-distinct path
    and a later round's presorted sort mode), the shared single-key sort at
    ROWS u32 keys, the four programs between the acceptance's sorts, and
    the acceptance-and-move program.  The acceptance's programs hold no
    loop: the bit-serial search is refine's alone."""
    def s(shape, dt):
        return _spec(one_chip, shape, dt)

    fn, args, static = _cluster_stage(stage, s)
    with jax.enable_x64(True):
        text = jax.jit(fn, static_argnames=tuple(static)).lower(
            *args, **static).compile().as_text()
    if stage == "sort":
        assert f"u32[{ROWS}]" in text and "u64" not in text
    elif stage not in ("round0", "round-sort"):
        assert " while(" not in text
