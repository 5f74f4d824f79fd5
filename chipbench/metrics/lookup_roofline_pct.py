"""The lookup kernels' share of their roofline: the least time of the
window's lookups (least bytes over the chip's HBM peak; the kernels are
memory-bound) over their device time from the trace."""
from pathlib import Path

from chipbench import roofline
from chipbench.harness import load_module

_kernel = load_module(Path(__file__).with_name("lookup_kernel_ms.py"))


def read(ctx):
    seconds = _kernel.kernel_seconds(ctx)
    if seconds <= 0 or ctx.lookup_bytes is None:
        return None
    sc = (ctx.counters_after["routed_shortcut"]
          - ctx.counters_before["routed_shortcut"])
    tr = (ctx.counters_after["routed_traditional"]
          - ctx.counters_before["routed_traditional"])
    trad = tr / (sc + tr) if sc + tr else 0.0
    weighted = ctx.lookup_bytes()
    per_request = (sum(w * b.total(trad) for w, b in weighted)
                   / sum(w for w, _ in weighted))
    least_s = (per_request * ctx.requests
               / roofline.peaks(ctx.device_kind).hbm_bytes_per_s)
    return 100.0 * least_s / seconds
