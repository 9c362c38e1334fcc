"""dense_topk_roofline.{open,closed} — dense scan and fused top-k kernel
(``kernels/ash_score.py`` ``_dense_kernel``), profiler trace.

Share (%) of the kernel's device time that its operations and bytes
need at the chip's peaks (``bench/roofline.py`` ``dense_topk_call``,
one call per trace operation matching PATTERN, the pallas_call
of ``ops.ash_score_topk``)."""
from bench import layers, roofline

PATTERN = r"^ash_score_topk_pallas(\.\d+)?$"


def read(ctx):
    k_tilde = layers.shortlist(ctx)
    return layers.kernel_roofline(
        ctx, PATTERN,
        lambda m: roofline.dense_topk_call(ctx.shapes, m, k_tilde),
    )
