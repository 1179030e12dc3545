"""Deterministic elastic data layer.

Finishes what the reference only sketched (its distributed data layer is
WIP/non-functional — SURVEY §2 C21: undefined names, excluded from ctest):

- ``dataset``    — file-list datasets and record splitters
  (≙ python/edl/collective/dataset.py ``FileSplitter/TxtFileSplitter``).
- ``checkpoint`` — per-(file, record) progress for exact mid-epoch resume
  (≙ the ``DataCheckpoint`` sketch, python/edl/collective/data_reader.py:63-84).
- ``dispatcher`` — leader-hosted task-queue dispatch service
  (todo/pending/done/failed with timeout+retry, state snapshot for
  failover — the full behavior of the reference's legacy Go master,
  pkg/master/service.go:23-35, re-built on the edl_tpu wire protocol;
  the native C++ twin lives in ``native/master``).
- ``loader``     — the worker-side iterator: pulls shards from the
  dispatcher, yields batches, records progress.
- ``prefetch``   — fixed-shape batching (pad+mask, XLA static shapes) and
  host->device prefetch with bounded in-flight transfers (net-new: the
  reference has no device-feed stage at all).
- ``block_diffusion`` — the forward (noising) process of a block-diffusion
  training step, on the host, as a function of explicit randomness and of a
  batch's place in the data order.
"""

from edl_tpu.data.dataset import FileListDataset, FileSplitter, TxtFileSplitter
from edl_tpu.data.block_diffusion import noise_draws, noised, noised_batch
from edl_tpu.data.checkpoint import DataCheckpoint
from edl_tpu.data.dispatcher import (
    DISPATCH_SERVICE,
    DataDispatcher,
    DataTask,
    DispatcherClient,
    discover_dispatcher,
    publish_dispatcher,
)
from edl_tpu.data.loader import ElasticDataLoader
from edl_tpu.data.prefetch import batched, prefetch_to_device, shuffled

__all__ = [
    "DISPATCH_SERVICE",
    "discover_dispatcher",
    "publish_dispatcher",
    "FileListDataset",
    "FileSplitter",
    "TxtFileSplitter",
    "DataCheckpoint",
    "DataDispatcher",
    "DispatcherClient",
    "DataTask",
    "ElasticDataLoader",
    "batched",
    "noise_draws",
    "noised",
    "noised_batch",
    "prefetch_to_device",
    "shuffled",
]
