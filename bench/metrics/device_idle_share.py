"""device_idle_share.{open,closed} — device, profiler trace.

1 - busy / window over a steady stretch inside the window, in %: how far
the host holds the chip back."""
from bench import layers


def read(ctx):
    return layers.idle_share(ctx)
