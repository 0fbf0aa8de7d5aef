"""Of the device-idle seconds inside ``perfbench.handover``, the share that
no childless program span covers: what the program's tracing cannot name."""
from perfbench import program_spans as ps


def read(ctx):
    gaps = ps.handover_idle(ctx)
    if not gaps:
        return None
    return 100.0 * sum(s for s, _, named in gaps if not named) / sum(s for s, _, _ in gaps)
