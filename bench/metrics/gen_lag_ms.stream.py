"""95th percentile, in ms, of how late the load generator sent each
request of the window behind its due time."""

from bench.loadgen import quantile


def read(ctx):
    if not ctx.gen_lag_s:
        return None
    return quantile(ctx.gen_lag_s, 0.95) * 1e3
