"""Device milliseconds per run of the jitted decode program, from the
trace: operation time inside each run wholly in the traced window,
averaged over those runs."""
from chipbench import view


def read(run):
    return view.decode_ms(run)
