"""90th percentile, over the requests due in the window, of the prompt
feed: from taking a slot to the first token (``req.t_first_token -
req.t_admit``, the engine's own stamps), the prompt fed one token a step;
in a traced run, over the requests due before the profiler session, each
less its part in the two stretches where starting and stopping the
session held the benchmark's loop (``spans.stamp_p90``)."""
from chipbench import spans


def read(run):
    return spans.stamp_p90(run, "t_admit", "t_first_token")
