"""The operations and bytes a fused leaf dispatch NEEDS, from its shapes.

One dispatch answers one shard's part of one panel: for `series` rows it must
read every sample between the first window's start and the last window's end
(the reset correction and the running sums need all of them), one base value
and one group id per series, and write one f32 per group and window.  Samples
outside that span, padding rows and padding lanes are not needed by the
algorithm, so they are not counted: a kernel that reads whole padded rows has
that against it in its roofline share.
"""


def needed_samples(span_s, range_s, scrape_ms):
    """Samples per series in (first window end - range, last window end]."""
    return (span_s + range_s) * 1000 // scrape_ms


def fused_leaf(series, span_s, range_s, step_s, scrape_ms, groups):
    """{"bytes", "flops"} of one dispatch over `series` rows."""
    cols = needed_samples(span_s, range_s, scrape_ms)
    windows = span_s // step_s + 1
    bytes_ = series * cols * 4 + series * (4 + 4) + groups * windows * 4
    # per sample: a difference and a compare-select for resets, or an add for
    # the running sum; per series and window: ~12 for the extrapolation; the
    # group sum as a one-hot matmul: 2 * groups per series and window
    flops = series * cols * 3 + series * windows * (12 + 2 * groups)
    return {"bytes": bytes_, "flops": flops}


def least_seconds(cost, peak):
    """(seconds, bound): the least time the chip could take for `cost`."""
    by_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    by_flops = cost["flops"] / peak["bf16_flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops, "flops")
