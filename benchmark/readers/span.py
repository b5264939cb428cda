"""Seconds of one of the harness's own spans around a call into the
program (benchmark's clock)."""


def read(ctx, name):
    return ctx["spans"].get(name)
