"""The operations and bytes a RAGGED PHASED fused dispatch needs, from its
shapes (`costs.py` counts a dense leaf on one shared timestamp row).

One dispatch is one launch of the fused program: the working sets of every
leaf of a request, `series` rows in all, each row on the scrape grid behind
it by a phase of its own and with NaN in the slots where it holds no sample.
The algorithm must read every slot between the first window's start and the
last window's end once (f32; whether a slot holds a sample is in the value),
for every series its base value, its group id and its phase (4 bytes each),
and write two f32 per group and window: the sum and the count of series
present, which on ragged rows is no function of the window alone.  Per slot
read: the validity test, the carry of the first and of the last valid sample
(one select each way) and the running count: 4; per series and window about
12 for the extrapolation and two one-hot matmuls (sums, presence): 2 x 2 x
groups.  Slots outside the span, padding rows and lanes, and the log2(T)
steps a shift-and-select scan takes in place of a carry are the kernel's
doing, not the algorithm's need, and are not counted: a sound reading cannot
pass 100%.
"""


def ragged_phased_launch(series, span_s, range_s, step_s, scrape_ms, groups):
    """{"bytes", "flops"} of one launch over `series` rows in all."""
    cols = (span_s + range_s) * 1000 // scrape_ms
    windows = span_s // step_s + 1
    bytes_ = series * cols * 4 + series * (4 + 4 + 4) \
        + 2 * groups * windows * 4
    flops = series * cols * 4 + series * windows * (12 + 4 * groups)
    return {"bytes": bytes_, "flops": flops}
