"""``edl_train_bd_masked_share`` at the window's close: of the ``L`` noised
positions of the last step the loop waited for, the share the forward process
masked (and the loss scored), as the block-diffusion loss head counted it from
its weights. A health gauge like ``kda_decay_mean``, not a lever: **sound is
the band 0.5 +/- 0.02**, 0.5 being the mean of ``t`` under one noise level a
block drawn uniformly and 0.02 two and a half standard deviations of one step
of 2048 blocks of 4 (0.0078; the chip read 0.487 to 0.511 over PR 61's traced
runs). A reading outside the band on EITHER side is the fault (a forward
process that over-masks is no better than one that under-masks): ``BETTER``
and ``MOVES`` are the schema's fields and nothing more, since the schema has
no neutral direction, and no change to the program is meant to move this. A
forward process that masks by position and not by block reads the same, one
whose level is not uniform does not. A program without the loss head
publishes no such gauge: nothing to read."""

NAME = "bd_masked_share"
UNIT = "ratio"
BETTER = "higher"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    series = run.at_close["registry"].get("edl_train_bd_masked_share", {})
    return series.get("") or None
