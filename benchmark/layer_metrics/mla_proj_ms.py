"""Device 0's time a traced step under ``mla_proj`` (a latent-attention layer's
latent path: the query's and the keys' and values' projections, the latents'
norms, the rotation, the out projection; forward, recomputation and backward
alike, the multi-token module's block among the layers), by the program's
``obs/profile.py:step_scopes()``."""

from benchmark import kda_timeline

NAME = "mla_proj_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return kda_timeline.scope_ms(run, "mla_proj")
