"""Device-to-host pulls the V-cycle engine's host waits on per batch:
`vcycle.sync` spans over `buffcut.t3.vcycle` spans, both wholly inside the
traced window."""
from bench.stages import span_stats


def read(m: dict) -> float | None:
    sync = span_stats(m, "vcycle.sync")
    calls = span_stats(m, "buffcut.t3.vcycle")
    if sync is None or not calls or not calls["count"]:
        return None
    return sync["count"] / calls["count"]
