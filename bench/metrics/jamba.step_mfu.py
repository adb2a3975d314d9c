"""Model FLOPs of the rows the traced ticks carried (every live row through
the whole hybrid, ``adapter.token_flops``: projections, Mamba scan and the
attention layers at the row's context; the output head for the rows that
sampled a token), over the traced window's length times the chip's bf16
peak."""
import work


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0 or not run.rows:
        return None
    pub = run.cfg["published"]
    flops = sum(
        work.model_flops(rows, n,
                         lambda ctx: run.adapter.token_flops(pub, ctx),
                         pub["hidden_size"], pub["vocab_size"])
        for rows, n in zip(run.rows, run.sampled))
    return 100.0 * flops / (t["window_s"] * run.peak["flops_per_s"])
