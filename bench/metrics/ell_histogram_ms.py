"""Mean device milliseconds of one call of the Pallas `ell_histogram`
kernel in the traced window.

The trace reduction keeps every device event whose HLO text names the
kernel, and the fusions that read its result name it as an operand; the
kernel's own instruction is the event with exactly its three arrays, the
(K, B) counts and the two (W, B) tiles, as `kernel_cost.ell_histogram`
reads them.  The mean is over calls, not batches: a traced window of the
mesh cell can lose device events (PERF.md), and a call's time does not
depend on how many the trace kept.  None where the trace holds no call."""
from bench import kernel_cost


def calls(m: dict) -> list:
    """The kernel's own calls in the run's reduced trace."""
    own = []
    for c in ((m.get("trace") or {}).get("kernels") or {}).get("ell_histogram", []):
        if len(c["shapes"]) != 3:
            continue
        try:
            kernel_cost.ell_histogram(c["shapes"])
        except (ValueError, TypeError, KeyError):
            continue
        own.append(c)
    return own


def read(m: dict) -> float | None:
    own = calls(m)
    return 1000.0 * sum(c["seconds"] for c in own) / len(own) if own else None
