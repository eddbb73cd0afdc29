"""Block-masked grouped-query flash attention's share of its roofline in
this cell (32 query heads on 4 key/value heads of 128 over the two copies
of a row under the block-diffusion mask): operations and bytes of each
attention call the step makes (``counts/<family>.py:
blockdiff_attention_call``: the mask's live pairs, not "causal at half")
over the device time of the kernels that implement them
(``benchmark/patterns/kernels.flash_blockdiff_roofline/``). A call of the
last layer held has the noised copy's queries alone; which layer an event
belongs to is read from its ``op_name`` in the compiled text
(``layers_<i>``). An event on a recomputed path is credited with nothing
(the block keeps the kernel's outputs; were it to run twice, its time
would count and its work would not)."""

import re

from benchmark.harness import flops, layers, roofline, scopes

_LAYER = re.compile(r"(?:^|[/(])layers_(\d+)(?:[/)]|$)")


def read(ctx):
    if ctx["trace"] is None or not ctx["program"].get("hlo"):
        return None
    counts = flops.counts(ctx["config"])
    if not hasattr(counts, "blockdiff_attention_call"):
        return None
    rows, seq = ctx["traffic"]["rows_per_chip"], ctx["traffic"]["seq"]
    program = scopes.parse_hlo(ctx["program"]["hlo"])
    last = str(ctx["config"]["num_hidden_layers"] - 1)

    def work_of(kind, event):
        path = scopes.path_of(program, event.name)
        if layers.pass_of(path) == "recompute":
            return 0, 0
        layer = _LAYER.search(path)
        return counts.blockdiff_attention_call(
            ctx["config"], rows, seq, kind,
            clean_queries=layer is None or layer.group(1) != last)

    try:
        return roofline.share(ctx, "kernels.flash_blockdiff_roofline",
                              work_of)
    except LookupError:
        return None     # a program without these kernels: nothing to read
