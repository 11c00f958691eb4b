"""mfu_pct.mla: ``mfu_pct`` in a cell whose block counts its own model
FLOPs (its ``step_flops``: for ``mla_moe``, latent attention and the held
share's expected experts, prompt and output tokens only, never padding),
over the traced window's length times the chip's peak bf16 rate, in
percent. A block that keeps no count of its own reads as ``mfu_pct``."""
from harness import load_reader

_plain = load_reader("mfu_pct")


def read(run):
    block = run.cell.block()
    if not hasattr(block, "step_flops"):
        return _plain(run)
    tr = run.trace
    if tr is None or run.peak is None or tr["window_s"] <= 0:
        return None
    g = run.geometry
    flops = sum(block.step_flops(g, t.work, t.emitted)
                for t in run.traced_ticks())
    if flops <= 0:
        return None
    return 100.0 * flops / (tr["window_s"] * run.peak["bf16_flops_per_s"])
