"""The delta of some `/metrics` families over the delta of others, over a
phase of the run (`setup`, `window`): a mean per event where the program
counts both the events and what they carried (the groups of the window's
fused leaves over those leaves).  None where no event was booked, or where
the program has no such family (an older commit)."""


def read(ctx, counters, over, phase="window"):
    before, after = ctx["counters"][phase]
    if any(c not in after for c in counters):
        return None
    events = sum(after.get(c, 0.0) - before.get(c, 0.0) for c in over)
    if not events:
        return None
    return sum(after.get(c, 0.0) - before.get(c, 0.0)
               for c in counters) / events
