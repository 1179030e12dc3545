"""``edl_train_moe_bias_absmax`` at the window's close: the largest ``|b|`` of
an expert layer's balancing bias (the mean over the expert layers), as the
model sowed it in the last step the loop fetched. The bias enters the choice
``top_k(s + b)`` beside sigmoid scores in (0, 1) and moves by a fixed rate a
step towards balance: how large it has grown is how far the balancing has had
to lean against the router, and a bias that keeps growing is a router the rule
cannot balance (the held experts' grouped matmuls are then as long as the
imbalance makes them)."""

NAME = "expert_bias_absmax"
UNIT = "score"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "program_counter"


def read(run):
    return run.at_close["registry"].get("edl_train_moe_bias_absmax", {}).get("")
