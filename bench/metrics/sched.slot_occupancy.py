"""Mean share of the batch slots that emitted a token per tick, over the
window's ticks (a slot decodes one token a tick)."""


def read(run):
    ticks = run.t_close - run.t_open
    if ticks <= 0:
        return None
    tokens = sum(map(run.window_tokens, run.timeline))
    return 100.0 * tokens / (ticks * run.cfg["serving"]["slots"])
