"""paged_attn_roofline: the least time the traced ticks' paged-attention
calls could take (``bench/work.py``: for each layer's call, the larger of
its FLOPs over the peak and its KV, q and output bytes over the HBM
bandwidth, from the rows' token positions) over the device time of the
kernel's operations in the trace, in percent."""
import work


def read(run):
    tr = run.trace
    if tr is None or run.peak is None or tr["kernel_s"] <= 0:
        return None
    g = run.geometry
    least = sum(g.layers * work.kernel_min_seconds(g, t.work, run.peak)
                for t in run.traced_ticks())
    return 100.0 * least / tr["kernel_s"] if least > 0 else None
