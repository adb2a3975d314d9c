"""Least time the chip needs for the KV-cache attention of the rows served
in the hybrid's attention layers (``work.ragged_attention`` with the
adapter's layer count and heads: live rows, live K/V read once per tick
and layer), over the device time of the ``qragged_attn`` kernel's events
(the write and the attention call)."""
import work


def read(run):
    att = run.adapter.attention(run.cfg["published"])
    spent = (run.trace or {}).get("kernel_s", {}).get("qragged_attn", 0.0)
    if att is None or spent <= 0 or not run.rows:
        return None
    layers, hq, hkv, hd = att
    need = sum(work.least_time(*work.ragged_attention(rows, layers, hq, hkv,
                                                      hd), run.peak)
               for rows in run.rows)
    return 100.0 * need / spent
