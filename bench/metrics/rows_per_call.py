"""rows_per_call.closed — engine batching (``serving/engine.py``), the
program's counters ``EngineStats`` batched rows / batches over the window.

Real query rows per fused call."""
from bench import layers


def read(ctx):
    return layers.rows_per_call(ctx)
