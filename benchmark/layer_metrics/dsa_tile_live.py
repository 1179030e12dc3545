"""``edl_train_dsa_tile_live`` at the window's close: of the masked forward
kernel's (block_q, block_k) tiles that touch the causal triangle, the share that
holds at least one selected pair (the mean over the sparse-attention layers), as
the model sowed it in the last step the loop fetched. The masked flash kernels
compute every such tile whole, so one minus this is what a kernel that follows
the selection could skip; a fresh indexer on uniform tokens scatters its keys
over the whole prefix and reads near 1.0."""

NAME = "dsa_tile_live"
UNIT = "ratio"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    series = run.at_close["registry"].get("edl_train_dsa_tile_live", {})
    return series.get("") or None
