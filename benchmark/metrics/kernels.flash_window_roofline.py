"""Window-masked grouped-query flash attention's share of its roofline in
this cell (32 query heads on 4 key/value heads of 128, the query at ``i``
seeing the keys ``i - W + 1 .. i``): operations and bytes of each window
layer's attention call the step makes (``counts/<family>.py:
window_attention_call``: the band's visible pairs, not "causal at half")
over the device time of the kernels that implement them
(``benchmark/patterns/kernels.flash_window_roofline/``). An event on a
recomputed path is credited with nothing (the layer keeps the kernel's
outputs; were it to run twice, its time would count and its work would
not)."""

from benchmark.harness import flops, layers, roofline, scopes


def read(ctx):
    if ctx["trace"] is None or not ctx["program"].get("hlo"):
        return None
    counts = flops.counts(ctx["config"])
    if not hasattr(counts, "window_attention_call"):
        return None
    rows, seq = ctx["traffic"]["rows_per_chip"], ctx["traffic"]["seq"]
    program = scopes.parse_hlo(ctx["program"]["hlo"])

    def work_of(kind, event):
        if layers.pass_of(scopes.path_of(program, event.name)) == "recompute":
            return 0, 0
        return counts.window_attention_call(ctx["config"], rows, seq, kind)

    try:
        return roofline.share(ctx, "kernels.flash_window_roofline", work_of)
    except LookupError:
        return None     # a program without these kernels: nothing to read
