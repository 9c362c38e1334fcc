"""gen_lag_ms_p99.open — load generator (``bench/loadgen.py``), host clock.

99th percentile of send time minus due time over the window's requests:
how late the open-loop generator ran.  A starved generator is not a fast
server."""
from bench import layers


def read(ctx):
    return layers.generator_lag_ms(ctx, 0.99)
