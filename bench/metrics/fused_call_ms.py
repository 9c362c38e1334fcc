"""fused_call_ms.{open,closed} — fused search call
(``AshIndex.search_prepped`` under ``QueryEngine``), the program's span
``RequestStats.scoring_us``.

Median host-clock time of one fused call, to ``block_until_ready``,
counted once per call."""
from bench import layers


def read(ctx):
    return layers.fused_call_ms(ctx, 0.5)
