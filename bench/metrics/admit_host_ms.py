"""Session: host work to admit one request (page allocation, prompt
upload, prompt-write dispatch, page-map upload, token update): the median
over the window's ``session.admit`` spans of their duration less their
``.wait`` children (prefill and first-token reads)."""
from bench import spans


def read(ctx):
    return spans.median_host_ms(getattr(ctx, "spans", None) or [],
                                "session.admit")
