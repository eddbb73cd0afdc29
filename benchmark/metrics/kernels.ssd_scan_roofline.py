"""The chunked scan's share of its roofline: the work of a scan call
(``counts/<family>.py: scan_call``: forward, or backward at twice the
matmuls) for each call seen under the ``ssm_scan`` scope (one per Mamba
block and pass) over the device time of the events under it."""

from benchmark.harness import flops, layers, roofline


def read(ctx):
    folded = layers.calls_as_events(ctx, "ssm_scan")
    if folded is None:
        return None
    tokens = ctx["traffic"]["rows_per_chip"] * ctx["traffic"]["seq"]
    scan_call = flops.counts(ctx["config"]).scan_call

    def work_of(kind, event):
        return scan_call(ctx["config"], tokens, kind)

    return roofline.share(folded, "kernels.ssd_scan_roofline", work_of)
