"""Model FLOP/s utilization: items per second per chip (as ``throughput``
takes them) x the operations the forward and backward passes need for one
item (the family's ``flops_per_item``, from shapes, recomputation not
counted) over the chip's peak bfloat16 rate (``peaks.json``, exact
``device_kind``)."""

NAME = "mfu"
UNIT = "%"
BETTER = "higher"
SOURCE = "host_clock"


def read(run):
    if not run.step_s or run.peaks is None:
        return None
    per_chip = run.items_per_step / run.step_s / run.chips
    flops = run.family.flops_per_item(run.config)
    return 100.0 * per_chip * flops / run.peaks["bf16_flops_per_s"]
