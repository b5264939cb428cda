"""The bytes and operations a LAUNCH of the tiled-band fused program needs,
from its shapes (`costs.py` counts one leaf of an hour-long row).

One launch answers one request: `*_over_time` of every series the request
selects, summed (or averaged) by group.  The algorithm must read every
sample between the first window's start and the last window's end once
(f32), one base value and one group id a series, and write one f32 per group
and window.  Per sample one add into its window's sum; per series and window
the division and the group sum as a one-hot product: 2 x groups.  Samples
outside that span, padding rows, lanes and windows (13 of a tile of 128),
and the six bf16 passes that an exact f32 product takes on the MXU are the
kernel's doing, not the algorithm's need, and are not counted: a sound
reading cannot pass 100%.
"""


def band_launch(series, span_s, range_s, step_s, scrape_ms, groups):
    """{"bytes", "flops"} of one launch over `series` rows in all, into
    `groups` groups in all."""
    cols = (span_s + range_s) * 1000 // scrape_ms
    windows = span_s // step_s + 1
    bytes_ = series * cols * 4 + series * (4 + 4) + groups * windows * 4
    flops = series * cols + series * windows * (1 + 2 * groups)
    return {"bytes": bytes_, "flops": flops}
