"""Least time the chip could take for the gated convolutions' work / the device
time under ``sconv_conv``. The work is the family's ``sconv_conv_flops`` (two
gates, ``L`` tap products and their sums a channel a token; forward and
backward) and ``sconv_conv_bytes`` (three reads and one write of ``[tokens,
hidden]`` forward, four reads and three writes backward, in the compute dtype):
the same count whether plain XLA or a kernel runs it. What remat computes and
reads a second time is not counted as work, and is counted as time. The work is
bound by HBM (under one operation a byte against the v5e's 240); the larger
bound is taken whichever it is."""

from benchmark import lfm2_timeline

NAME = "sconv_conv_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    flops = getattr(run.family, "sconv_conv_flops", None)
    if flops is None or run.peaks is None:
        return None
    seconds = lfm2_timeline.scope_seconds(run, ("sconv_conv",))
    if not seconds:
        return None
    tokens = run.items_per_step // run.chips * run.trace["steps"]
    least = max(
        flops(run.config, tokens) / run.peaks["bf16_flops_per_s"],
        run.family.sconv_conv_bytes(run.config, tokens) / run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / seconds
