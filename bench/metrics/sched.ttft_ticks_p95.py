"""95th percentile of ticks from arrival to the end of the tick that emitted
the first token, over every request that arrived in the window; a request
still waiting at the close counts the ticks it has waited."""
import numpy as np


def read(run):
    waits = [(r["first_tick"] + 1 if r["first_tick"] is not None
              else run.t_close) - r["arrival"]
             for r in run.timeline if run.in_window(r["arrival"])]
    return float(np.percentile(waits, 95)) if waits else None
