"""Mean milliseconds of the partition worker's batch-model build
(`buffcut.t3.batch_model` spans wholly inside the traced window)."""
from bench.stages import span_stats


def read(m: dict) -> float | None:
    s = span_stats(m, "buffcut.t3.batch_model")
    return 1000.0 * s["sum_s"] / s["count"] if s and s["count"] else None
