"""mfu_pct: the model FLOPs the traced ticks needed (``bench/work.py``:
prompt and output tokens only, never padding) over the traced window's
length times the chip's peak bf16 rate, in percent."""
import work


def read(run):
    tr = run.trace
    if tr is None or run.peak is None or tr["window_s"] <= 0:
        return None
    g = run.geometry
    per_layer = run.cell.block().layer_matmul_params(g)
    flops = sum(work.step_flops(g, t.work, t.emitted, per_layer)
                for t in run.traced_ticks())
    if flops <= 0:
        return None
    return 100.0 * flops / (tr["window_s"] * run.peak["bf16_flops_per_s"])
