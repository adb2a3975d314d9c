"""Least time the chip needs for the KV-cache attention of the rows served
(``work.ragged_attention``: live rows, live K/V read once per tick and
layer), over the device time of the ``qragged_attn`` kernel's events."""
import work


def read(run):
    att = run.adapter.attention(run.cfg["published"])
    spent = (run.trace or {}).get("kernel_s", {}).get("qragged_attn", 0.0)
    if att is None or spent <= 0:
        return None
    layers, hq, hkv, hd = att
    need = 0.0
    for rows in run.rows:
        f, b = work.ragged_attention(rows, layers, hq, hkv, hd)
        need += work.least_time(f, b, run.peak)
    return 100.0 * need / spent
