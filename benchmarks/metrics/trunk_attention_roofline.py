"""The sparse-attention kernel's share of the chip's bf16 peak: the FLOP
attention over the selected keys requires an iteration
(``costs_attention.selected_attention_flops_per_iteration``) over the
device time of the kernel's events, forward and backward (Mosaic calls
named ``sparse_flash_*``, ``ops/sparse_attention.py``), per iteration. The
compute roof binds: the kernel computes every visible key of a tile under
the mask, in three bf16 passes, recomputed in the backward pass, so the
share stays far below 100%. Nothing where no such kernel runs."""

from benchmarks import costs_attention


def read(context):
    cell = context["cell"]
    work = costs_attention.selected_attention_flops_per_iteration(cell.config, cell.job)
    seconds = sum(
        total for key, (_, total) in context["trace"].get("kernel_s", {}).items()
        if "sparse_flash" in key
    )
    if work is None or not seconds:
        return None
    achieved = work * context["iterations"] / cell.chips / seconds
    return 100.0 * achieved / context["peaks"]["bf16_flops_per_s"]
