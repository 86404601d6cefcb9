"""Share of its roofline, in %, that the Pallas `ell_histogram` kernel
reaches in the traced window: per call the larger of its operations over
the chip's peak FLOP/s and its bytes over the peak bandwidth
(`kernel_cost.ell_histogram` from the call's shapes), summed over the
kernel's own calls and divided by their measured time.  None where the
trace holds no call."""
from bench import kernel_cost
from bench.metrics.ell_histogram_ms import calls


def read(m: dict) -> float | None:
    own = calls(m)
    if not own:
        return None
    return kernel_cost.roofline_share(own, kernel_cost.ell_histogram, m["device_kind"])
