"""Share of the traced window (full-width steps, one resize, steps at the
smaller width) in which no operation ran, averaged over the chips."""
from chipbench import view


def read(run):
    return view.idle_pct(run)
