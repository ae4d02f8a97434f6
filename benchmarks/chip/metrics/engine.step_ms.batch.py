"""Mean host milliseconds of one engine ``step_once`` over the window
(benchmark clock around the call, which ends in a host read)."""
from chipbench import view


def read(run):
    return view.engine_step_ms(run)
