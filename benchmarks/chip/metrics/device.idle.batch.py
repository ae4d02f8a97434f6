"""Share of the traced window in which no operation ran on the chip."""
from chipbench import view


def read(run):
    return view.idle_pct(run)
