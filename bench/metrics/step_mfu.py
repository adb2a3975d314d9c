"""Model FLOPs of the rows the window's ticks carried (every live row
through the whole model, the output head for the rows that sampled a
token), over the traced window's length times the chip's bf16 peak."""
import work


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    pub = run.cfg["published"]
    flops = sum(
        work.model_flops(rows, n, lambda ctx: run.adapter.token_flops(pub,
                                                                      ctx),
                         pub["hidden_size"], pub["vocab_size"])
        for rows, n in zip(run.rows, run.sampled))
    return 100.0 * flops / (t["window_s"] * run.peak["flops_per_s"])
