"""Milliseconds per dispatch on the HOST's clock around dispatch + readback,
from `/admin/devices` (utils/devicetelem): delta seconds over delta count of
the kernels whose name matches `pattern`, over the window."""
import re


def read(ctx, pattern):
    before, after = ctx["kernels"]
    rx = re.compile(pattern)
    count = secs = 0.0
    for name, (c, s) in after.items():
        if rx.search(name):
            c0, s0 = before.get(name, (0, 0.0))
            count += c - c0
            secs += s - s0
    return 1000.0 * secs / count if count else None
