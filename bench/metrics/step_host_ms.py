"""Session: host time per ``runtime.step()`` during which the step put no
work on the chip: the median over the window's ``runtime.step`` spans of
their duration less their ``.wait`` descendants' (the host blocked on the
device)."""
from bench import spans


def read(ctx):
    return spans.median_host_ms(getattr(ctx, "spans", None) or [],
                                "runtime.step")
