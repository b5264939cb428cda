"""The operations and bytes a fused HISTOGRAM leaf dispatch needs, from its
shapes (`costs.py` counts a series as one kernel row; here a series is one
row a bucket).

One dispatch answers one shard's part of one panel.  A histogram series of
B buckets is B kernel rows, each a counter: for `series x B` rows the kernel
must read every sample between the first window's start and the last
window's end, one base value and one (group, bucket) slot id per row, and
write one f32 per group, bucket and window.  Per sample a difference and a
compare-select (resets) and the running pick: 3; per row and window about 12
for the extrapolation; the slot sum as a one-hot matmul: 2 x groups x B per
row and window.  Samples outside the span, padding rows and lanes, and the
transpose that makes the rows are not needed by the algorithm and are not
counted, so a sound reading cannot pass 100%.
"""


def hist_fused_leaf(series, buckets, span_s, range_s, step_s, scrape_ms,
                    groups):
    """{"bytes", "flops"} of one dispatch over `series` histogram rows."""
    rows = series * buckets
    cols = (span_s + range_s) * 1000 // scrape_ms
    windows = span_s // step_s + 1
    slots = groups * buckets
    bytes_ = rows * cols * 4 + rows * (4 + 4) + slots * windows * 4
    flops = rows * cols * 3 + rows * windows * (12 + 2 * slots)
    return {"bytes": bytes_, "flops": flops}
