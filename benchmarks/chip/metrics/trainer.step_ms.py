"""Mean host milliseconds of the trainer's ``step_once`` at the full data
width over the window (benchmark clock; each step ends in a host read of
its loss)."""
from chipbench import view


def read(run):
    return view.train_step_ms(run)
