"""idle_in_call_share.{open,closed} — engine host path inside a fused
call (``serving/engine.py`` ``_run_batch``: prep, fetch, scatter), the
program's span ``ash.call`` over the profiler's device trace.

Share (%) of the traced window in which the device is idle while an
``ash.call`` span is open.  With ``idle_between_calls_share`` it adds up
to ``device_idle_share``."""
from bench import spans


def read(ctx):
    split = spans.idle_split(ctx.trace)
    return None if split is None else split[0]
