"""Device 0's time a traced step under ``moe_latent`` (a latent expert layer's two
shared projections, model width -> latent before the dispatch and latent -> model
width after the combine; forward, recomputation and backward alike), by the
program's ``obs/profile.py:step_scopes()``."""

from benchmark import latent_moe_timeline

NAME = "moe_latent_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "Model + kernels"
MOVES = "throughput"
SOURCE = "device_trace"


def read(run):
    return latent_moe_timeline.scope_ms(run)
