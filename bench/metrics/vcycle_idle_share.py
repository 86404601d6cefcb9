"""Share of the traced window, in %, in which the device idled while the
partition worker was inside a V-cycle call (`buffcut.t3.vcycle` and the
engine's `vcycle.*` spans within it): packing, scalar pulls and launches
between the level programs."""
from bench.stages import ENGINE, span_stats


def read(m: dict) -> float | None:
    if span_stats(m, "buffcut.t3.vcycle") is None:
        return None
    idle = m["trace"]["idle_by_span"]
    return 100.0 * sum(idle.get(n, 0.0) for n in ("buffcut.t3.vcycle", *ENGINE)) / m["trace"]["window_s"]
