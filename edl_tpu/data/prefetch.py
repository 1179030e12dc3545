"""Fixed-shape batching + host->device prefetch for the input pipeline.

The piece between ``ElasticDataLoader``'s raw-record stream and a jitted
train step. The reference leaves batching to Paddle's reader decorators
(example/collective/resnet50/train_with_fleet.py:458-464) and has no
device-feed stage at all (data loading and GPU compute serialize unless
Paddle's double-buffer flag is set). On TPU the rules are stricter and
the win is bigger:

  - XLA wants STATIC shapes: every batch must be exactly ``batch_size``,
    so the ragged final batch is padded and carries a validity mask the
    loss can apply (never a smaller array — that would retrace/recompile).
  - HBM should never wait on the host: ``prefetch_to_device`` keeps
    ``depth`` batches in flight, transferring batch N+1 (and N+2) while
    the step consumes batch N, with an optional ``jax.sharding.Sharding``
    so dp-sharded batches land directly on their mesh slices.
"""

from __future__ import annotations

import collections
import queue
import threading
from typing import Any, Callable, Iterable, Iterator, Optional, Tuple

import numpy as np

from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import trace as obs_trace

__all__ = ["batched", "prefetch_to_device", "shuffled"]

# counted where the step loop takes a batch off the queue, so that their
# ratio is made at the one place the loop can starve
_M_BATCHES = obs_metrics.counter(
    "edl_data_prefetch_batches_total",
    "device batches the consumer took off the prefetch queue",
)
_M_STARVED = obs_metrics.counter(
    "edl_data_prefetch_starved_total",
    "batches the consumer had to wait for: it found the prefetch queue "
    "empty (an epoch's first batch always counts)",
)


def shuffled(records: Iterable[Any], buffer_size: int, seed: int) -> Iterator[Any]:
    """Streaming shuffle through a bounded reservoir (tf.data-style).

    Deterministic for a given ``seed`` — pass an epoch-derived seed to
    keep the reference's ``pass_id_as_seed`` reproducible-order contract
    (train_with_fleet.py:458-464) while decorrelating batches. O(buffer)
    memory however long the stream."""
    if buffer_size < 1:
        raise ValueError("buffer_size must be >= 1")
    rng = np.random.RandomState(seed)
    buf: list = []
    for rec in records:
        if len(buf) < buffer_size:
            buf.append(rec)
            continue
        idx = rng.randint(buffer_size)
        out, buf[idx] = buf[idx], rec
        yield out
    rng.shuffle(buf)
    yield from buf


def batched(
    records: Iterable[Any],
    batch_size: int,
    collate: Optional[Callable[[list], Any]] = None,
    drop_remainder: bool = False,
) -> Iterator[Tuple[Any, np.ndarray]]:
    """Group a record stream into fixed-size batches.

    Yields ``(batch, mask)`` where ``mask`` is a ``(batch_size,)`` bool
    array — all True except on a padded final batch, whose tail repeats
    the last real record (values are valid arrays, mask tells the loss
    which rows count). ``collate`` turns the list of records into the
    batch structure (default: ``np.stack`` of per-record arrays, or a
    tuple of stacked fields when records are tuples).
    """
    if batch_size <= 0:
        raise ValueError("batch_size must be positive")
    collate = collate or _default_collate
    buf: list = []
    for rec in records:
        buf.append(rec)
        if len(buf) == batch_size:
            yield collate(buf), np.ones((batch_size,), bool)
            buf = []
    if buf and not drop_remainder:
        mask = np.zeros((batch_size,), bool)
        mask[: len(buf)] = True
        while len(buf) < batch_size:
            buf.append(buf[-1])
        yield collate(buf), mask


def _default_collate(records: list):
    first = records[0]
    if isinstance(first, tuple):
        return tuple(
            np.stack([np.asarray(r[i]) for r in records])
            for i in range(len(first))
        )
    return np.stack([np.asarray(r) for r in records])


class _Stop:
    pass


def prefetch_to_device(
    batches: Iterable[Any],
    depth: int = 2,
    sharding=None,
    epoch: Optional[int] = None,
) -> Iterator[Any]:
    """Iterate ``batches`` with ``depth`` device transfers in flight.

    A daemon thread pulls host batches and ``jax.device_put``s them
    (honouring ``sharding`` when given — e.g. ``NamedSharding(mesh,
    P("dp"))`` to scatter the leading axis across the dp mesh axis), so
    the transfer of the next batch overlaps the step on the current one.
    Exceptions in the source iterator are re-raised at the consuming
    call site. Staging HBM is bounded at ``depth + 1`` device batches:
    the queue holds at most ``depth`` and the feeder stages the next
    batch before blocking on the queue reservation.

    The feeder thread leaves three spans a batch, numbered ``batch`` as
    the consumer will see them and labelled with ``epoch`` when the
    caller gives one: ``feed_next`` (the source iterator), ``feed_put``
    (staging on the device) and ``feed_queue`` (blocked on a full queue:
    the healthy state, since then the consumer is the slower side).
    """
    import jax

    if depth < 1:
        raise ValueError("depth must be >= 1")
    q: queue.Queue = queue.Queue(maxsize=depth)
    err: collections.deque = collections.deque(maxlen=1)
    stop = threading.Event()  # consumer gone: unblock + stop the feeder
    label = {} if epoch is None else {"epoch": epoch}
    tracer = obs_trace.get_tracer()

    def put(batch):
        if sharding is None:
            return jax.tree.map(jax.device_put, batch)
        # local-rows semantics on cross-process meshes (each process
        # contributes its own rows of the global batch)
        from edl_tpu.parallel.mesh import device_put_local_rows

        return jax.tree.map(
            lambda a: device_put_local_rows(a, sharding), batch
        )

    def feeder():
        try:
            source = iter(batches)
            k = 0
            while True:
                with tracer.span("feed_next", batch=k, **label):
                    try:
                        b = next(source)
                    except StopIteration:
                        break
                with tracer.span("feed_put", batch=k, **label):
                    staged = put(b)
                with tracer.span("feed_queue", batch=k, **label):
                    while not stop.is_set():
                        try:
                            q.put(staged, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                if stop.is_set():
                    return  # abandoned mid-epoch: drop staged batches
                k += 1
        except BaseException as exc:  # re-raised consumer-side
            err.append(exc)
        finally:
            while not stop.is_set():  # deliver _Stop unless abandoned
                try:
                    q.put(_Stop, timeout=0.1)
                    break
                except queue.Full:
                    continue

    t = threading.Thread(target=feeder, daemon=True, name="edl-prefetch")
    t.start()
    try:
        while True:
            starved = q.empty()
            item = q.get()
            if item is _Stop:
                if err:
                    raise err.popleft()
                return
            _M_BATCHES.inc()
            if starved:
                _M_STARVED.inc()
            yield item
    finally:
        # runs on break/exception/GeneratorExit too: without it the
        # feeder blocks in q.put forever, pinning `depth` device batches
        stop.set()
        while True:
            try:
                q.get_nowait()
            except queue.Empty:
                break
