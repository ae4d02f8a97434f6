"""Device-idle milliseconds per traced engine step under the engine's
``engine.decode``, ``engine.sample`` or ``engine.readback`` span: the chip
waiting on the round trip that samples each token, from the decode
program's launch through the arg-max dispatch to the read-back of the
tokens.  The clock anchors fix this sum in every step that admits
nothing, and to within 0.1-0.25 ms a step over the window on a TPU v5e;
how it splits among the three spans is uncertain by the anchor
interval's width (1.5-1.9 ms there), so the parts are not read apart
(``spans.ROUND_TRIP``)."""
from chipbench import spans


def read(run):
    a = spans.attribute_run(run)
    return a.per_step_ms(*spans.ROUND_TRIP) if a else None
