"""Seconds the last pass of one of the program's background jobs took
(`/admin/jobs`), if that pass started inside the window."""


def read(ctx, job):
    row = ctx["jobs"].get(job)
    lo, hi = ctx["window_epochs"]
    if row is None or not lo <= row["lastStartUnixSeconds"] <= hi:
        return None
    return row["lastDurationSeconds"]
