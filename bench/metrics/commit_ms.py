"""Mean milliseconds of the partition worker's commit of a batch: labels,
loads, cut increment, IER (`buffcut.t3.commit` spans wholly inside the
traced window)."""
from bench.stages import span_stats


def read(m: dict) -> float | None:
    s = span_stats(m, "buffcut.t3.commit")
    return 1000.0 * s["sum_s"] / s["count"] if s and s["count"] else None
