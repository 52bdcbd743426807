"""Time the host spent paused in the run's window: the program's
``process.gc`` (garbage collections) and ``jax.compile`` spans there,
summed.  A persistent-cache load (``jax.cache_load``) runs inside a
``jax.compile`` span and is not added again.  A window with no pause reads
0, where the program records pauses at all."""

import program


def read(ctx):
    rows = program.alg1_window(ctx)
    if rows is None or not program.has_pause_spans():
        return None
    return 1e3 * program.seconds(rows, program.PAUSES)
