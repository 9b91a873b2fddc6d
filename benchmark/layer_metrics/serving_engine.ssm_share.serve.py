"""Share of the serving step's device time in the state-space layers'
phases (``lib/phases.py``): ``ssm.proj`` (the in and out projections, dt
and A), ``ssm.conv`` (the causal conv with its state, and the scan's
layouts), ``ssm.scan`` (the scan kernel) and ``ssm.gate`` (the gated
norm). A program without those phases gives nothing to read."""
from lib import phases, ssm_kernels as sk


def read(record, cell):
    if sk.phase_seconds(record, cell, sk.SSM_PHASES) is None:
        return None
    return phases.share(record, cell, "serve", sk.SSM_PHASES)
