"""Host time per solve in the recovery LP: the program's
``session.recovery_solve`` spans in the run's window (one per straggler
pattern not seen before), over the solves completed.  A window whose
patterns were all seen before reads 0."""

import program


def read(ctx):
    rows = program.alg1_window(ctx)
    if rows is None:
        return None
    return 1e3 * program.seconds(rows, ("session.recovery_solve",)) / ctx["counters"]["solves"]
