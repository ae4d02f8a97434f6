"""90th percentile, over the requests due in the window, of the engine's
queue wait: from submission to taking a slot (``req.t_admit -
req.t_submit``, the engine's own stamps).  With the prompt feed it makes
up the time to first token, less how late the generator submitted.
In a traced run only the requests due before the profiler session count,
each less its part in the two stretches where starting and stopping the
session held the benchmark's loop: stopping it holds the loop for
seconds, and the queue it leaves lasts through the rest of the window
(``spans.stamp_p90``)."""
from chipbench import spans


def read(run):
    return spans.stamp_p90(run, "t_submit", "t_admit")
