"""Share (%) of the window's responses whose `field` equals `equals`."""


def read(ctx, field, equals):
    res = ctx["results"]
    if not res:
        return None
    return 100.0 * sum(1 for r in res if r.get(field) == equals) / len(res)
