#!/usr/bin/env python3
"""Smoke run of the streaming partitioner on one TPU chip.

Drives the main path once, through the entry points a user calls, at the
paper's batch size, and checks what comes out.  One process runs every
phase in turn (a chip belongs to one process):

  preflight  a TPU must be JAX's default device; device kind, count and JAX
             version are printed first.  Without a TPU the script exits 2
             before anything else runs.
  kernels    the compiled Pallas `block_histogram` at the shapes the main
             path admits, against its jnp reference (kernels/ref.py); the
             compiled program must hold a `tpu_custom_call`.
  main path  a seeded RMAT graph (n = 2^20, average degree 16) written as
             packed .bcsr, partitioned by `repro.api.partition` with the
             pipelined driver at k=32, Q=262144, δ=32768, d_max=10000, HAA
             (configs/buffcut_paper.py) on engine="jax", then on the numpy
             engine="sparse": labels bit-identical, no engine fallback, and
             the streamed cut equal to the recomputed one.
  CLI        `python -m repro partition` in-process with its default
             --engine auto (the Pallas `ell` engine on a TPU) against
             --engine sparse on a smaller graph: equal cuts.
  serving    the main-path result promoted to a PartitionService: lookups,
             edge inserts and deletes, one refine; the resident cut must
             equal the cut recomputed from the exported graph.

The last line of stdout is {"ok": true, "device": {...}} and nothing else;
a failed phase exits non-zero without it.  `--size tiny` runs every phase
at a tiny size and may run off the chip; it never prints the ok line.

    python chip_smoke.py                               # on the chip
    JAX_PLATFORMS=cpu python chip_smoke.py --size tiny # rehearsal
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# scale: log2 n of the main-path graph; cli_scale: of the CLI graph;
# hist_rows: the pow2 node bucket of one δ-batch plus its k aux nodes
SIZES = {
    "paper": dict(scale=20, cli_scale=16, degree=16, k=32, buffer=262144,
                  batch=32768, d_max=10000.0, hist_rows=65536),
    "tiny": dict(scale=12, cli_scale=10, degree=16, k=32, buffer=1024,
                 batch=256, d_max=10000.0, hist_rows=512),
}
SEED = 0
DATA_DIR = ROOT / ".smoke_data"  # listed in .gitignore; removed on exit


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class CompileMeter:
    """Backend compiles (a load from the persistent cache counts as one),
    their seconds, and the persistent cache's hits, from JAX's monitoring
    events."""

    def __init__(self):
        from jax import monitoring

        self.compiles = self.cache_hits = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return dict(compiles=self.compiles, compile_s=self.seconds,
                    cache_hits=self.cache_hits)

    def since(self, snap: dict) -> dict:
        """What was counted after `snap` ({} for everything)."""
        now = self.snapshot()
        out = {k: now[k] - snap.get(k, 0) for k in now}
        out["compile_s"] = round(out["compile_s"], 2)
        return out


def preflight(size: str):
    import jax

    devs = jax.devices()
    dev = devs[0]
    log("preflight", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devs), jax=jax.__version__)
    if dev.platform != "tpu" and size != "tiny":
        print("chip_smoke: JAX found no TPU; only --size tiny runs off the "
              "chip", file=sys.stderr)
        sys.exit(2)
    return dev, len(devs)


def phase_kernels(sz: dict, on_tpu: bool) -> None:
    """Compiled kernels at the main path's shapes vs the jnp references."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    if on_tpu and not ops.USE_KERNELS_DEFAULT:
        raise AssertionError("kernels are off on a TPU backend")
    interpret = not on_tpu
    rng = np.random.default_rng(SEED)
    rows, k = sz["hist_rows"], sz["k"]
    cases = [(rows, w, k) for w in (8, 128, 256)]
    cases.append((4096, 64, 4096))  # a K-tiled cluster-label domain
    for b, w, kk in cases:
        blk = jnp.asarray(rng.integers(-1, kk, (b, w)).astype(np.int32))
        # small integer weights: f32 sums are exact, so compare exactly
        wts = jnp.asarray((rng.integers(1, 5, (b, w)) * (np.asarray(blk) >= 0))
                          .astype(np.float32))
        t0 = time.perf_counter()
        got = np.asarray(ops.block_histogram(blk, wts, kk, use_kernel=True,
                                             interpret=interpret))
        dt = time.perf_counter() - t0
        want = np.asarray(ops.block_histogram(blk, wts, kk, use_kernel=False))
        if not np.array_equal(got, want):
            raise AssertionError(f"block_histogram {b}x{w} k={kk} != ref")
        log("kernels", block_histogram=f"{b}x{w}", k=kk, seconds=round(dt, 3))
    if on_tpu:
        text = ops.block_histogram.lower(
            blk, wts, kk, use_kernel=True, interpret=False).compile().as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError("compiled block_histogram has no Pallas call")


def _timed_batches(engine: str):
    """Wrap the multilevel entry the drivers call, to time each batch (and
    report progress every 8 batches)."""
    import repro.core.multilevel as ml

    real = ml.multilevel_partition
    times: list[float] = []

    def timed(*args, **kw):
        t0 = time.perf_counter()
        try:
            return real(*args, **kw)
        finally:
            times.append(time.perf_counter() - t0)
            if len(times) % 8 == 0:
                log("main", engine=engine, batches_done=len(times),
                    last_batch_s=round(times[-1], 3))

    ml.multilevel_partition = timed
    return times, lambda: setattr(ml, "multilevel_partition", real)


def phase_main(sz: dict, path: Path, meter: CompileMeter) -> tuple:
    import numpy as np

    import repro.core.multilevel_jax as mlj
    from repro.api import partition
    from repro.core import edge_cut
    from repro.graphs import rmat_graph
    from repro.graphs.stream_io import write_packed

    t0 = time.perf_counter()
    g = rmat_graph(1 << sz["scale"], sz["degree"], seed=SEED)
    write_packed(g, str(path))
    log("main", graph=f"rmat n={g.n} m={g.m} max_degree={g.max_degree}",
        gen_s=round(time.perf_counter() - t0, 1))
    kw = dict(driver="buffcut-pipe", k=sz["k"], buffer_size=sz["buffer"],
              batch_size=sz["batch"], d_max=sz["d_max"], score="haa")
    runs = {}
    for engine in ("jax", "sparse"):
        mlj.reset_trace_counts()
        times, restore = _timed_batches(engine)
        before = meter.snapshot()
        t0 = time.perf_counter()
        try:
            res = partition(str(path), engine=engine, **kw)
        finally:
            restore()
        wall = time.perf_counter() - t0
        cut = edge_cut(g, res.labels)
        if cut != res.stats.cut_weight:
            raise AssertionError(
                f"{engine}: streamed cut {res.stats.cut_weight} != {cut}")
        rest = times[1:] or [0.0]
        runs[engine] = res
        log("main", engine=engine, cut_ratio=res.cut_ratio,
            balance=res.balance, wall_s=round(wall, 2),
            nodes_per_s=round(g.n / wall), batches=len(times),
            first_batch_s=round(times[0], 2) if times else None,
            rest_batch_mean_s=round(sum(rest) / len(rest), 3),
            engine_fallbacks=res.stats.engine_fallbacks,
            **meter.since(before))
        if engine == "jax":
            log("main", trace_counts=json.dumps(mlj.trace_counts()))
    if runs["jax"].stats.engine_fallbacks != 0:
        raise AssertionError("the jax engine fell back to the host engine")
    if not np.array_equal(runs["jax"].labels, runs["sparse"].labels):
        diff = int((runs["jax"].labels != runs["sparse"].labels).sum())
        raise AssertionError(f"jax and sparse labels differ on {diff} nodes")
    return runs["jax"], g


def phase_cli(sz: dict, path: Path) -> None:
    import numpy as np

    import repro.core.multilevel as ml
    from repro.api import PartitionResult
    from repro.api.cli import main as cli_main
    from repro.core import edge_cut
    from repro.graphs import rmat_graph
    from repro.graphs.stream_io import write_packed

    g = rmat_graph(1 << sz["cli_scale"], sz["degree"], seed=SEED + 1)
    write_packed(g, str(path))
    real = ml.label_histogram_ell
    calls = [0]

    def counted(*a, **kw):
        calls[0] += 1
        return real(*a, **kw)

    cuts = {}
    for engine in ("auto", "sparse"):
        out = path.with_suffix(f".{engine}.json")
        argv = ["partition", str(path), "-k", str(sz["k"]), "--json", str(out)]
        if engine != "auto":  # auto is the CLI default: no flag
            argv += ["--engine", engine]
        ml.label_histogram_ell = counted
        t0 = time.perf_counter()
        try:
            rc = cli_main(argv)
        finally:
            ml.label_histogram_ell = real
        if rc != 0:
            raise AssertionError(f"python -m repro partition exited {rc}")
        res = PartitionResult.from_json(str(out))
        cuts[engine] = edge_cut(g, res.labels)
        log("cli", engine=engine, cut=cuts[engine],
            wall_s=round(time.perf_counter() - t0, 2),
            ell_histogram_calls=calls[0] if engine == "auto" else 0)
    if cuts["auto"] != cuts["sparse"]:
        raise AssertionError(f"CLI auto cut {cuts['auto']} != sparse "
                             f"{cuts['sparse']}")


def phase_serve(res, g) -> None:
    import numpy as np

    from repro.core import edge_cut

    t0 = time.perf_counter()
    svc = res.into_service()
    rng = np.random.default_rng(SEED + 2)
    nodes = rng.integers(0, g.n, 64)
    if not np.array_equal(svc.lookup(nodes), res.labels[nodes]):
        raise AssertionError("service lookup != partition labels")
    # inserts between random pairs, deletes of existing edges
    ins = [(int(u), int(v)) for u, v in rng.integers(0, g.n, (16, 2)) if u != v]
    dels = []
    for u in rng.integers(0, g.n, 64):
        nb = g.neighbors(int(u))
        if nb.size and len(dels) < 16 and (int(u), int(nb[0])) not in dels \
                and (int(nb[0]), int(u)) not in dels:
            dels.append((int(u), int(nb[0])))
    svc.update(insert_edges=ins)
    svc.update(delete_edges=dels)
    info = svc.refine()
    cut = edge_cut(svc.export_graph(), svc.labels)
    if svc.cut_weight != cut:
        raise AssertionError(f"resident cut {svc.cut_weight} != {cut}")
    log("serve", inserts=len(ins), deletes=len(dels),
        refined=info.get("moved", info.get("batches")), cut=cut,
        wall_s=round(time.perf_counter() - t0, 2))


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--size", choices=sorted(SIZES), default="paper",
                    help="paper: the chip run; tiny: a rehearsal of every "
                         "phase that may run off the chip")
    args = ap.parse_args(argv)
    sz = SIZES[args.size]

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    dev, count = preflight(args.size)
    meter = CompileMeter()
    on_tpu = dev.platform == "tpu"
    log("preflight", compile_cache=cache, size=args.size)
    t_all = time.perf_counter()
    seconds = {}
    main_out = []  # the main path's (result, graph), for the service phase
    DATA_DIR.mkdir(exist_ok=True)
    try:
        for name, fn in (
            ("kernels", lambda: phase_kernels(sz, on_tpu)),
            ("main", lambda: main_out.extend(
                phase_main(sz, DATA_DIR / "rmat.bcsr", meter))),
            ("cli", lambda: phase_cli(sz, DATA_DIR / "rmat_cli.bcsr")),
            ("serve", lambda: phase_serve(*main_out)),
        ):
            t0, before = time.perf_counter(), meter.snapshot()
            fn()
            seconds[name] = round(time.perf_counter() - t0, 2)
            log(name, status="ok", seconds=seconds[name],
                **meter.since(before))
    except Exception:
        traceback.print_exc()
        print(f"chip_smoke: phase {name!r} failed", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(DATA_DIR, ignore_errors=True)
    log("summary", **seconds, total_s=round(time.perf_counter() - t_all, 2),
        **meter.since({}))
    if args.size != "paper" or not on_tpu:
        print("chip_smoke: rehearsal passed; no ok line", flush=True)
        return 0
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": count}
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
