"""Device-idle milliseconds per traced engine step under the engine's
``engine.admit`` span, its ``engine.reset_slot`` spans included: the chip
waiting while the host admits requests and resets their cache rows.  The
idle next to the operations a reset runs is placed only to within the
clock anchors' interval: on a TPU v5e it moved 0.04-0.1 ms a step from
one end of the interval to the other (``chipbench.spans``)."""
from chipbench import spans


def read(run):
    a = spans.attribute_run(run)
    return a.per_step_ms("engine.admit", "engine.reset_slot") if a else None
