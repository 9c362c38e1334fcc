"""idle_between_calls_share.{open,closed} — driver and batching
(``serving/frontend.py`` ``_drive``, ``QueryEngine.poll`` /
``flush_ready``, ``max_wait_s``), the program's span ``ash.call`` over
the profiler's device trace.

Share (%) of the traced window in which the device is idle and no
``ash.call`` span is open.  With ``idle_in_call_share`` it adds up to
``device_idle_share``."""
from bench import spans


def read(ctx):
    split = spans.idle_split(ctx.trace)
    return None if split is None else split[1]
