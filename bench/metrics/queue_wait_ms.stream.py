"""Median, in ms, of the scheduler's own `queue_wait` spans (admission to
dispatch) of the requests sent in the window."""

import statistics


def read(ctx):
    if not ctx.queue_wait_s:
        return None
    return statistics.median(ctx.queue_wait_s) * 1e3
