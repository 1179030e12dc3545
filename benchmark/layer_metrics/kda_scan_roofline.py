"""Least time the chip could take for the Kimi delta rules' work / the device
time under ``kda_scan``. The work is the family's ``kda_scan_flops`` (the chunked
form's products at the source's chunk of 64, the causal half inside a chunk,
the solve as forward substitution would do it; forward and backward) and
``kda_scan_bytes`` (q, k, v, the log-decay a key channel, beta and o once each
way, and their gradients); what remat computes twice, what the sub-blocks spend
on columns the mask drops and what the chosen solve spends on blocks of zeros
are not counted as work, and are counted as time. The rule is plain XLA, not
one kernel, so the time is the scope's, the L2 norms, beta and the gate with it."""

from benchmark import kda_timeline

NAME = "kda_scan_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    seconds = kda_timeline.scope_seconds(run, ("kda_scan",))
    if not seconds:
        return None
    tokens = run.items_per_step // run.chips * run.trace["steps"]
    return kda_timeline.roofline(run, seconds, "kda_scan_flops", "kda_scan_bytes", tokens)
