"""Host time per request at the fleet's front door: the mean
`fleet.submit` span (`ReplicatedSolverFleet.submit`, its wait for the
router's lock included)."""
from bench import program_trace as pt

UNIT = "ms"


def read(ctx):
    return pt.mean_ms(ctx, "fleet.submit")
