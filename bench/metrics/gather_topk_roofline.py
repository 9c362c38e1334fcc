"""gather_topk_roofline.{open,closed} — IVF masked-gather kernel
(``kernels/ash_score.py`` ``_gather_kernel``), profiler trace.

Share (%) of the kernel's device time that its operations and bytes
need at the chip's peaks (``bench/roofline.py`` ``gather_topk_call``,
one call per trace operation matching PATTERN, the pallas_call of
``ops.ash_score_gather_topk``).  The XLA gather that lays the probed
rows out for the kernel, the probe, the merge and the rerank run
outside these operations and are not counted."""
from bench import layers, roofline

PATTERN = r"^ash_score_gather_topk_pallas(\.\d+)?$"


def read(ctx):
    k_tilde = layers.shortlist(ctx)
    nprobe = int(ctx.search["nprobe"])
    return layers.kernel_roofline(
        ctx, PATTERN,
        lambda m: roofline.gather_topk_call(ctx.shapes, m, nprobe, k_tilde),
    )
