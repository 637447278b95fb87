"""Device-idle time per job while the host is inside one of the
program's stage spans (``round1``, ``allocate``, ``round2``,
``final_solve``): what the host's dispatch and waits inside the job cost
the device."""
from chipbench import scopes


def read(ctx):
    return scopes.stage_gap_ms_per_job(ctx)
