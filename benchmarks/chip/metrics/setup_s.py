"""Set-up time (s): from the start of the process to the start of the
window: imports, reaching the chip, weights, compilation or cache loads,
and the warm-up at the cell's own shapes.  Host clock."""


def read(run):
    return run.setup_s
