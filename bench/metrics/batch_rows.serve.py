"""Query rows served per dispatch in the run's window, from the frontend's
own counters."""


def read(ctx):
    c = ctx["counters"]
    return c["rows"] / c["dispatches"] if c.get("dispatches") else None
