"""Mean milliseconds of the V-cycle engine's packing and copy up, from the
call's entry to the first level's dispatch (`vcycle.pack` spans wholly
inside the traced window)."""
from bench.stages import span_stats


def read(m: dict) -> float | None:
    s = span_stats(m, "vcycle.pack")
    return 1000.0 * s["sum_s"] / s["count"] if s and s["count"] else None
