"""The program's stage spans in a profiler trace, and their reduction
(bench/stages.py), on the CPU."""
import importlib

import pytest

from bench import stages, trace

W, V = "measured window", "V-cycle call"
READERS = ("task_wait_share", "batch_model_ms", "commit_ms", "pack_ms",
           "syncs_per_batch", "vcycle_idle_share")
# the handmade trace of bench/test_bench_trace.py: window [90, 500), busy
# [100, 250) and [400, 450), the harness's V-cycle call over [90, 290)
OLD = {"ops": [[0, 50], [100, 100], [150, 100], [400, 50], [410, 20], [2000, 10]],
       "modules": [["jit_a", 95, 60], ["jit_b", 150, 110], ["jit_a", 390, 70]],
       "spans": [[W, 90, 410], [V, 90, 200]],
       "kernels": []}
T2, T3 = "/host:CPU#0", "/host:CPU#1"
PROGRAM = [
    ["buffcut.t3.task_wait", 60, 31, T3],   # straddles the window's open
    ["buffcut.t3.vcycle", 92, 208, T3],
    ["vcycle.pack", 92, 6, T3],
    ["vcycle.sync", 260, 20, T3],
    ["buffcut.t3.commit", 300, 30, T3],
    ["buffcut.t3.task_wait", 330, 30, T3],
    ["buffcut.t3.batch_model", 360, 35, T3],
    ["buffcut.t3.vcycle", 395, 125, T3],    # straddles the window's close
    ["vcycle.sync", 460, 10, T3],
    ["buffcut.t2.block", 250, 170, T2],     # T2 labels no idle time
]


def test_span_names_match_the_program():
    from repro.core.spans import SPANS

    assert stages.PROGRAM_SPANS == SPANS


def test_reduction_of_a_handmade_trace_with_program_spans():
    ev = {**OLD, "program_spans": PROGRAM}
    r = stages.reduce_events(ev, W, V)
    old = trace.reduce_events(OLD, W, V)
    for key in ("window_s", "busy_s", "device_ops", "kernels"):
        assert r[key] == old[key]
    s = r["spans"]
    assert set(s) == {"buffcut.t3.task_wait", "buffcut.t3.vcycle", "vcycle.pack",
                      "vcycle.sync", "buffcut.t3.commit", "buffcut.t3.batch_model",
                      "buffcut.t2.block"}
    assert s["buffcut.t3.vcycle"] == {"union_s": pytest.approx(313e-9), "count": 1,
                                      "sum_s": pytest.approx(208e-9)}
    assert s["buffcut.t3.task_wait"] == {"union_s": pytest.approx(31e-9), "count": 1,
                                         "sum_s": pytest.approx(30e-9)}
    assert s["vcycle.sync"]["count"] == 2
    assert s["buffcut.t2.block"]["union_s"] == pytest.approx(170e-9)
    want = {"none": 1, "buffcut.t3.task_wait": 31, "vcycle.pack": 6,
            "buffcut.t3.vcycle": 77, "vcycle.sync": 30, "buffcut.t3.commit": 30,
            "buffcut.t3.batch_model": 35}
    assert r["idle_by_span"] == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["t3_cover_s"] == pytest.approx(409e-9)
    assert r["idle_gaps"] == [[f"outside {V} / buffcut.t3.commit", pytest.approx(150e-9)],
                              [f"outside {V} / buffcut.t3.vcycle", pytest.approx(50e-9)],
                              [f"inside {V} / vcycle.pack", pytest.approx(10e-9)]]

    m = {"trace": r}
    got = {name: importlib.import_module(f"bench.metrics.{name}").read(m) for name in READERS}
    assert got == {"task_wait_share": pytest.approx(100 * 31 / 410),
                   "batch_model_ms": pytest.approx(35e-6),
                   "commit_ms": pytest.approx(30e-6),
                   "pack_ms": pytest.approx(6e-6),
                   "syncs_per_batch": 2.0,
                   "vcycle_idle_share": pytest.approx(100 * (77 + 6 + 30) / 410)}


def test_a_trace_without_program_spans_reduces_as_before():
    for ev in (OLD, {**OLD, "program_spans": []}):
        r = stages.reduce_events(ev, W, V)
        old = trace.reduce_events(OLD, W, V)
        assert {k: r[k] for k in old} == old
        assert r["spans"] == {} and r["t3_cover_s"] == 0
        assert r["idle_by_span"] == {"none": pytest.approx(r["window_s"] - r["busy_s"])}
    assert stages.reduce_events({"ops": [], "modules": [], "spans": [], "kernels": []},
                                W, V) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_the_spans(name):
    reader = importlib.import_module(f"bench.metrics.{name}")
    assert reader.read({"trace": None}) is None  # an untraced run
    assert reader.read({"trace": trace.reduce_events(OLD, W, V)}) is None  # bench/trace.py alone
    assert reader.read({"trace": stages.reduce_events(OLD, W, V)}) is None  # no program spans


def test_pipelined_job_emits_every_span(tmp_path, jax_state):
    """A small `buffcut-pipe` job on the jax engine under the profiler:
    every span name is in the trace, the engine's spans nest in T3's
    V-cycle span on T3's line, and one batch model is built per batch."""
    import glob

    import jax

    from repro.api import partition
    from repro.graphs import rmat_graph

    g = rmat_graph(1024, 8, seed=5)
    jax.profiler.start_trace(str(tmp_path))
    try:
        # queue depth 1 so T2 waits behind T3; d_max 60 leaves 11 hubs
        res = partition(g, driver="buffcut-pipe", engine="jax", k=4, buffer_size=128,
                        batch_size=64, d_max=60.0, queue_depth=1, prefetch_batches=1)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    ev = trace.extract(path, stages.PROGRAM_SPANS)
    assert {s[0] for s in ev["spans"]} == set(stages.PROGRAM_SPANS)
    spans = stages.extract_spans(path)
    calls = [(s, s + d, line) for n, s, d, line in spans if n == "buffcut.t3.vcycle"]
    for name, s, d, line in spans:
        if name.startswith("vcycle."):
            assert any(a <= s and s + d <= b and line == ln for a, b, ln in calls), name
    count = {n: sum(s[0] == n for s in spans) for n in stages.PROGRAM_SPANS}
    st = res.stats
    assert st.n_hubs > 0
    assert count["buffcut.t3.batch_model"] == st.n_batches == count["buffcut.t3.vcycle"]
    assert count["buffcut.t3.hub"] == st.n_hubs
    assert set(st.stage_s) == {n for n in stages.PROGRAM_SPANS
                               if n.startswith(("buffcut.t2.", "buffcut.t3."))}
    assert st.ml_time_s == st.stage_s["buffcut.t3.vcycle"] > 0
