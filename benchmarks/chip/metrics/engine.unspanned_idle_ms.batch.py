"""Device-idle milliseconds per traced engine step under no program span:
the benchmark's loop between steps, and whatever the engine's spans miss."""
from chipbench import spans


def read(run):
    a = spans.attribute_run(run)
    return a.per_step_ms(spans.UNSPANNED) if a else None
