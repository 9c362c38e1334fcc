"""queue_wait_ms_p50.open — frontend and engine queue
(``serving/frontend.py``, ``serving/engine.py``), the program's span
``RequestStats.queue_wait_s``.

Median wait of a request from submit to the start of its fused call."""
from bench import layers


def read(ctx):
    return layers.queue_wait_ms(ctx, 0.5)
