"""Step programs: the prefill programs' share of the chip's peak FLOP/s.

FLOPs each prefill needs (``bench/work.py``: 2 x active parameters per
token plus causal attention) over the device time of the prefill program
runs in the trace, over the peak. Each host ``prefill`` event inside the
traced window is matched to the program runs that start within it."""
from bench import trace_reduce, work

SLACK_S = 0.005


def read(ctx):
    if ctx.trace is None or ctx.trace_iv is None:
        return None
    runs = trace_reduce.program_runs(ctx.trace, "prefill_step")
    t0, t1 = ctx.trace_iv
    flops = dev_s = 0.0
    for s, e, lp, _ in ctx.prefill:
        if s < t0 or e > t1:
            continue
        mine = [d for start, d in runs
                if s - t0 - SLACK_S <= start <= e - t0 + SLACK_S]
        if mine:
            flops += work.prefill_flops(ctx.conf, lp)
            dev_s += sum(mine)
    if dev_s <= 0:
        return None
    return 100.0 * flops / dev_s / ctx.peaks["bf16_flops_per_s"]
