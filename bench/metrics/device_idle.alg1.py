"""Share of the traced stretch in which no operation ran on a chip,
averaged over the chips the cell uses."""


def read(ctx):
    return 100.0 * ctx["trace"]["idle_share"]
