"""Score tiles the attention kernels walk under the block-diffusion mask over
the (query, key) pairs the mask leaves visible, both as shares of the ``2 L x
2 L`` rectangle, the mean of the forward's walk and the backward's. From the
program's span ring, one source: the ``attn_tiles`` instants ``ops/attention.py``
leaves under the mask (``interior + edge`` of a kernel's census at its own
blocks over ``visible``, the mask's exact pair count). A kernel's own note says
``path`` ``kernel``. Off the TPU the dense reference runs and the note
(``path`` ``plain``, ``why`` ``backend``) holds the census at the blocks the
shape would get: a rehearsal reads the plan. A note that says a pass on the
chip took the reference, or a forward without a backward, is nothing walked:
nothing to read. 1.0 would be a walk of visible pairs alone; a causal walk over
the same positions reads above 2.0, and a noised block's own 1024 x 1024 tile
of 4096 visible pairs is what holds this above 1.0. A program without the mask
leaves no such instant: nothing to read."""

NAME = "attn_walked_over_live"
UNIT = "ratio"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    walked = {}
    for ev in run.tracer_events or ():
        args = ev.get("args") or {}
        if ev.get("name") != "attn_tiles" or args.get("mask") != "block_diffusion":
            continue
        if args.get("path") != "kernel" and args.get("why") != "backend":
            return None
        walked[args["kernel"]] = (args["interior"] + args["edge"]) / args["visible"]
    if "flash2_fwd" not in walked or len(walked) < 2:
        return None
    return sum(walked.values()) / len(walked)
