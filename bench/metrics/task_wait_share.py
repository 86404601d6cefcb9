"""Share of the traced window, in %, in which the partition worker waited
for its next task (`buffcut.t3.task_wait` spans, their union): T3 starved
by the stream reader (T1) or the buffer and batch selection (T2)."""
from bench.stages import span_stats


def read(m: dict) -> float | None:
    s = span_stats(m, "buffcut.t3.task_wait")
    return None if s is None else 100.0 * s["union_s"] / m["trace"]["window_s"]
