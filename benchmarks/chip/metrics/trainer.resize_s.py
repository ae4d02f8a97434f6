"""Mean host seconds from a platform event's delivery to the end of the
``poll_events`` call that resized the mesh (checkpoint, rebuild, reshard)."""


def read(run):
    r = run.record.resizes
    return sum(e - s for s, e, _, _ in r) / len(r) if r else None
