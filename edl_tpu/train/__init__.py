"""Training plane. Names resolve on first use (PEP 562), so importing a
jax-free submodule — the launcher takes ``CacheExchange`` from
``edl_tpu.train.aot`` — does not import jax through this package: a
control-plane process must never load it (see cluster/job_env.py). What a
name's first use imports is a ``package_import`` span in the ring (the
package's own body imports nothing)."""

import importlib

from edl_tpu.obs import trace as _trace

_HOME = {
    "current_env": "context",
    "enable_compilation_cache": "context",
    "init": "context",
    "worker_barrier": "context",
    "topk_compression": "compression",
    "ElasticTrainer": "loop",
    "piecewise_decay": "schedules",
    "scaled_schedule_factory": "schedules",
    "warmup_cosine": "schedules",
    "AUCState": "metrics",
    "auc_compute": "metrics",
    "auc_init": "metrics",
    "auc_merge": "metrics",
    "auc_update": "metrics",
    "TrainState": "step",
    "create_state": "step",
    "cross_entropy_loss": "step",
    "make_cross_entropy_loss": "step",
    "make_eval_step": "step",
    "make_block_diffusion_loss": "step",
    "make_kd_loss": "step",
    "make_masked_train_step": "step",
    "make_train_step": "step",
    "mse_loss": "step",
}

__all__ = sorted(_HOME)


def __getattr__(name):
    if name not in _HOME:
        raise AttributeError(
            "module %r has no attribute %r" % (__name__, name)
        )
    home = "%s.%s" % (__name__, _HOME[name])
    with _trace.package_import(home):
        value = getattr(importlib.import_module(home), name)
    globals()[name] = value
    return value
