"""``edl_train_moe_groups_live`` at the window's close: the groups of experts
(hosts, in the deployment) that hold at least one of a token's chosen experts,
the mean over tokens and expert layers, as the model sowed it in the last step
the loop fetched. The choice is limited to a token's best ``topk_group`` groups,
so this is at most that (4 of 8 here) and reads it where the limit binds: the
number of hosts a token's row would travel to in the exchange."""

NAME = "expert_groups_live"
UNIT = "groups"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    series = run.at_close["registry"].get("edl_train_moe_groups_live", {})
    return series.get("") or None
