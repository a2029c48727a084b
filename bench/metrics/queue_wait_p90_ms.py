"""Scheduler: 90th percentile of the wait from a request's due time to
the start of its prefill (the program's ``prefill`` event, ``t - wall_s``),
over the requests due in the window; one not yet prefilled when the window
closes counts with the time it has waited."""
import numpy as np


def read(ctx):
    waits = [(r.prefill_start if r.prefill_start is not None
              and r.prefill_start <= ctx.w1 else ctx.w1) - r.due
             for r in ctx.recs]
    return float(np.percentile(waits, 90)) * 1e3 if waits else None
