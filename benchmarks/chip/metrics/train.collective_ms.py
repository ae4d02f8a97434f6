"""Device milliseconds per traced training step spent in collective
operations (all-reduce, all-gather, reduce-scatter, permutes), averaged
over the chips."""
from chipbench import view


def read(run):
    spans = [a for a in view.trace_spans(run, "bench.trainer_step")]
    if not spans:
        return None
    return run.trace.collective_s() / len(spans) * 1e3
