"""Host time per solve spent on the job's data: the program's
``session.pack`` spans in the run's window (the fingerprint of the caller's
array, and on a miss the per-worker pack), over the solves completed."""

import program


def read(ctx):
    rows = program.alg1_window(ctx)
    if not rows or not any(r["name"] == "session.pack" for r in rows):
        return None
    return 1e3 * program.seconds(rows, ("session.pack",)) / ctx["counters"]["solves"]
