"""Least time the chip could take for the gated delta rules' work / the device
time under ``gdn_scan``. The work is the family's ``gdn_scan_flops`` (the
chunked form's products at the source's chunk of 64, the causal half inside a
chunk, the solve as forward substitution would do it; forward and backward) and
``gdn_scan_bytes`` (q, k, v, g, beta and o once each way, and their
gradients); what remat computes twice and what the chosen solve spends on
blocks of zeros are not counted as work, and are counted as time. At the
published widths the work is bound by HBM (about 80 operations a byte against
the v5e's 240); the larger bound is taken whichever it is. The rule is plain
XLA, not one kernel, so the time is the scope's, the L2 norms, beta and the
log-decay with it."""

from benchmark import gdn_timeline

NAME = "gdn_scan_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    flops = getattr(run.family, "gdn_scan_flops", None)
    if flops is None or run.peaks is None:
        return None
    seconds = gdn_timeline.scope_seconds(run, ("gdn_scan",))
    if not seconds:
        return None
    tokens = run.items_per_step // run.chips * run.trace["steps"]
    least = max(
        flops(run.config, tokens) / run.peaks["bf16_flops_per_s"],
        run.family.gdn_scan_bytes(run.config, tokens) / run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / seconds
