"""Step programs: the decode step's share of its roofline.

For each decode program run in the trace, the least time the chip could
take for that step (``bench/work.py``: the larger of its FLOPs over peak
FLOP/s and its needed bytes over peak bandwidth; decode is bound by the
bytes) over its device time, summed over the traced window's steps. A
run is matched to the harness step (``runtime.step()`` call) it starts
in, which knows the live slots' positions."""
from bench import trace_reduce, work

SLACK_S = 0.005


def read(ctx):
    if ctx.trace is None or ctx.trace_iv is None:
        return None
    runs = trace_reduce.program_runs(ctx.trace, "serve_step")
    t0, t1 = ctx.trace_iv
    bound = dev_s = 0.0
    for s, e, positions, _ in ctx.steps:
        if s < t0 or e > t1 or not positions:
            continue
        mine = [d for start, d in runs
                if s - t0 - SLACK_S <= start <= e - t0 + SLACK_S]
        if len(mine) != 1:
            continue
        bound += work.bound_seconds(work.decode_flops(ctx.conf, positions),
                                    work.decode_bytes(ctx.conf, positions),
                                    ctx.peaks)
        dev_s += mine[0]
    if dev_s <= 0:
        return None
    return 100.0 * bound / dev_s
