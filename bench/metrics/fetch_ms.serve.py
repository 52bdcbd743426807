"""Mean duration of the program's ``serve.fetch`` spans over the run (the
few dispatches set-up makes to warm each shape included): the blocking
copy of one dispatch's answers to the host, which waits for the compiled
assignment to finish."""

import program


def read(ctx):
    got = program.span_totals("serve.fetch")
    return 1e3 * got[1] / got[0] if got else None
