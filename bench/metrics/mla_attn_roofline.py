"""mla_attn_roofline: the least time the traced ticks' latent-attention
calls could take (the block's ``kernel_min_seconds``: for each layer's
call, the larger of its FLOPs over the peak and its latent, q and output
bytes over the HBM bandwidth, from the rows' token positions) over the
device time of the kernel's operations in the trace, in percent. Reads
nothing where the block has no latent kernel."""


def read(run):
    tr = run.trace
    block = run.cell.block()
    if (tr is None or run.peak is None or tr["kernel_s"] <= 0
            or not hasattr(block, "kernel_min_seconds")):
        return None
    g = run.geometry
    least = sum(g.layers * block.kernel_min_seconds(g, t.work, run.peak)
                for t in run.traced_ticks())
    return 100.0 * least / tr["kernel_s"] if least > 0 else None
