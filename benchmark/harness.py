"""What one benchmark run does around ``ElasticTrainer.fit``: the schedule of
epochs (warm-up, an optional traced epoch, the measured window), the clocks,
and the snapshots of the program's counters at the window's two ends.

The program is driven only through ``fit``'s own seams: ``data_fn(epoch)``,
which ``_fit_stage`` calls in the main thread at the top of every epoch
(after the previous epoch's sync, callback and save), and ``on_epoch_end``.
Nothing in the program is patched.
"""

from __future__ import annotations

import time

import jax


class WindowClosed(Exception):
    """Raised out of ``data_fn`` to leave ``fit`` once the deadline of a
    window made of whole epochs has passed (``epochs`` is fixed when ``fit``
    is called, and an empty epoch would save the same step twice)."""


def snapshot():
    """The program's counters, lanes and device memory, read in one place."""
    from edl_tpu.obs import goodput as obs_goodput
    from edl_tpu.obs import metrics as obs_metrics

    ledger = obs_goodput.ledger()
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()
    ]
    return {
        "registry": obs_metrics.default_registry().snapshot(),
        "goodput": {
            lane: ledger.seconds(lane)
            for lane in ("data_wait", "train", "compile", "ckpt_save", "restage")
        },
        "memory_peak_bytes": max(peaks) if peaks else 0,
    }


class CompileWatch:
    """Counts, on the benchmark's own account, every program XLA compiled or
    loaded from the persistent cache (jax's ``backend_compile_duration``
    event covers both), with the time it happened."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.times = []
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.times.append(time.monotonic())

    def between(self, t0, t1):
        return sum(1 for t in self.times if t0 <= t <= t1)


class Schedule:
    """The epochs of one run, and what was seen in each.

    Epoch 0 warms up (``warmup_steps``). With ``trace_dir`` set, epoch 1 is
    ``trace_steps`` long and runs under ``jax.profiler``. The epochs after
    that are the window: ``steps_per_epoch`` steps each until the first
    epoch boundary at or after ``seconds`` (then ``data_fn`` raises
    ``WindowClosed``), or, where ``steps_per_epoch`` is None, one epoch that
    stops feeding at the deadline (then ``fit`` returns by itself).
    """

    def __init__(self, mix, pool, seconds, trace_dir=None):
        self.mix = mix
        self.pool = pool
        self.seconds = float(seconds)
        self.trace_dir = trace_dir
        self.first_window_epoch = 2 if trace_dir else 1
        self.epochs = []          # one record per epoch started
        self.t_open = self.t_close = self.deadline = None
        self.at_open = self.at_close = None
        self.trace_window_ns = None  # (start, stop) on the wall clock, in ns
        self._served = 0

    # fit(epochs=...) for this schedule
    def epochs_argument(self):
        if self.mix["steps_per_epoch"] is None:
            return self.first_window_epoch + 1
        return 1_000_000

    def data_fn(self, epoch):
        now = time.monotonic()
        if self.epochs:
            self.epochs[-1]["t_next"] = now
        if self.trace_dir and epoch == 2:
            self._stop_trace()
        if epoch >= self.first_window_epoch:
            if self.t_open is None:
                self.at_open = snapshot()
                self.t_open = time.monotonic()
                self.deadline = self.t_open + self.seconds
            elif time.monotonic() >= self.deadline:
                self._close()
                raise WindowClosed()
        if epoch == 0:
            kind, steps = "warmup", self.mix["warmup_steps"]
        elif epoch < self.first_window_epoch:
            kind, steps = "trace", self.mix["trace_steps"]
            self._start_trace()
        else:
            kind, steps = "window", self.mix["steps_per_epoch"]
        record = {"epoch": epoch, "kind": kind, "steps": 0,
                  "t_data_fn": time.monotonic(), "t_yield": []}
        self.epochs.append(record)
        return self._batches(record, steps)

    def _batches(self, record, steps):
        """Cycles the pool. Runs in the prefetch feeder's thread; every batch
        it yields is dispatched as one step."""
        while True:
            if steps is None:
                if time.monotonic() >= self.deadline:
                    return
            elif record["steps"] >= steps:
                return
            with jax.profiler.TraceAnnotation("bench:host_batch"):
                batch = self.pool[self._served % len(self.pool)]
            self._served += 1
            record["steps"] += 1
            record["t_yield"].append(time.monotonic())
            yield batch

    def on_epoch_end(self, epoch, metrics):
        with jax.profiler.TraceAnnotation("bench:on_epoch_end"):
            record = self.epochs[-1]
            record["t_epoch_end"] = time.monotonic()
            # the last loss of the epoch, as fit has just synced it
            record["loss"] = float(metrics["loss"]) if "loss" in metrics else float("nan")
            if (
                self.mix["steps_per_epoch"] is None
                and epoch == self.first_window_epoch
            ):
                self._close()
            record["t_epoch_end_return"] = time.monotonic()

    def _close(self):
        self.t_close = time.monotonic()
        self.at_close = snapshot()

    def _start_trace(self):
        # the harness's annotations and the device; not every Python call and
        # runtime futex: at the default levels the tracer slowed the host's
        # input transfers several times over (PERF.md section 6, PR 22)
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 1
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.trace_window_ns = [time.time_ns(), None]

    def _stop_trace(self):
        self.trace_window_ns[1] = time.time_ns()
        jax.profiler.stop_trace()

    # -- what the window held ------------------------------------------------

    def window_epochs(self):
        return [e for e in self.epochs if e["kind"] == "window"]

    def window_steps(self):
        return sum(e["steps"] for e in self.window_epochs())

    def steps_dispatched(self):
        return sum(e["steps"] for e in self.epochs)

    def step_seconds(self):
        """``(seconds a step, how it was taken)`` over the window. One epoch
        to the deadline has no boundary inside it, so its cadence is the
        median over the stretches the device paced (``paced_stretches``). A
        window of whole epochs pays for its boundaries (syncs, saves, a
        drained pipeline), which are what such a mix is for: there, and where
        the feed shows fewer than ``MIN_STRETCHES``, it is window seconds over
        whole steps."""
        epochs = self.window_epochs()
        if self.mix["steps_per_epoch"] is None and len(epochs) == 1:
            stretches = paced_stretches(epochs[0]["t_yield"])
            if len(stretches) >= MIN_STRETCHES:
                return weighted_median(
                    [(s / n, n) for n, s in stretches]
                ), "median of %d paced stretches" % len(stretches)
        if not self.window_steps():
            return None, "no whole step"
        return (self.t_close - self.t_open) / self.window_steps(), "window mean"

    def boundary_events(self):
        """What the step loop did between two epochs (the program's
        ``on_epoch_end`` returning -> ``data_fn`` called: in a mix that saves,
        the fingerprint stamp and the save), as spans on the wall clock in the
        form of the program's tracer events, for the trace's idle gaps."""
        to_wall_us = (time.time() - time.monotonic()) * 1e6
        return [
            {"ph": "X", "name": "bench:epoch_boundary",
             "ts": e["t_epoch_end_return"] * 1e6 + to_wall_us,
             "dur": (e["t_next"] - e["t_epoch_end_return"]) * 1e6}
            for e in self.epochs if "t_next" in e and "t_epoch_end_return" in e
        ]

    def save_stalls(self):
        """Seconds the step loop stood still at each save of the window:
        ``on_epoch_end`` returning -> ``data_fn(epoch + 1)`` called."""
        return [
            e["t_next"] - e["t_epoch_end_return"]
            for e in self.window_epochs()
            if "t_next" in e and "t_epoch_end_return" in e
        ]


MIN_STRETCHES = 3


def paced_stretches(t_yield):
    """``[(steps, seconds)]``: the feed's timeline cut at every batch that
    had to wait.

    ``t_yield[k]`` is when the feeder thread asked for batch ``k``, which is
    when the step loop took an earlier one off the prefetch queue. The loop
    runs ahead of the device until something makes it wait for the device
    (the numerics plane fetches a finished step's bundle every few steps;
    failing that, the runtime's own queue fills), so batches are asked for in
    bursts, and the first of a burst is asked for a fixed few host
    instructions after the device finished a known step. Those are the
    marks: a batch that waited at least the mean interval. Between two marks
    the device ran ``steps`` whole steps in ``seconds``, whatever the host did
    in between. Where every batch waits alike (no bursts), about half are
    marks and the stretches are short; they still tile the timeline."""
    if len(t_yield) < 3:
        return []
    mean = (t_yield[-1] - t_yield[0]) / (len(t_yield) - 1)
    marks = [k for k in range(1, len(t_yield)) if t_yield[k] - t_yield[k - 1] >= mean]
    return [(b - a, t_yield[b] - t_yield[a]) for a, b in zip(marks, marks[1:])]


def weighted_median(pairs):
    """The value below which half the weight lies (``pairs`` of value, weight)."""
    pairs = sorted(pairs)
    half = sum(w for _, w in pairs) / 2.0
    seen = 0.0
    for i, (value, weight) in enumerate(pairs):
        seen += weight
        if seen > half:
            return value
        if seen == half:  # exactly between two values
            return (value + pairs[i + 1][0]) / 2.0
    return pairs[-1][0]


def on_one_device(tree, device):
    """``tree`` with every leaf a plain array on ``device``: a replicated
    leaf gives its own shard there, a sharded one goes through the host."""
    def move(leaf):
        if getattr(leaf, "is_fully_replicated", False):
            for shard in leaf.addressable_shards:
                if shard.device == device:
                    return shard.data
        return jax.device_put(jax.device_get(leaf), device)

    return jax.tree.map(move, tree)


def replica_digests(tree):
    """One exact digest per device over every fully replicated leaf: the sum
    of the leaves' bits as unsigned 32-bit integers, which wraps and so does
    not depend on the order of addition. Replicas that hold the same
    parameters give the same number."""
    import jax.numpy as jnp

    @jax.jit
    def digest(leaves):
        total = jnp.zeros((), jnp.uint32)
        for leaf in leaves:
            bits = jax.lax.bitcast_convert_type(leaf.astype(jnp.float32), jnp.uint32)
            total = total + jnp.sum(bits, dtype=jnp.uint32)
        return total

    per_device = {}
    for leaf in jax.tree.leaves(tree):
        if not getattr(leaf, "is_fully_replicated", False):
            continue
        for shard in leaf.addressable_shards:
            per_device.setdefault(shard.device, []).append(shard.data)
    return {str(d.id): int(digest(leaves)) for d, leaves in per_device.items()}


def restore_newest(ckpt_dir, job, mesh_axes, seed):
    """``(state, status)`` of the newest checkpoint under ``ckpt_dir``, read
    by a fresh ``CheckpointManager`` onto a template of shapes (nothing is
    initialised on the device again), replicated over the mix's mesh."""
    from edl_tpu.checkpoint import CheckpointManager
    from edl_tpu.parallel import make_mesh, replicated
    from edl_tpu.train import create_state

    with make_mesh(mesh_axes) as mesh:
        shapes = jax.eval_shape(
            lambda: create_state(
                job["model"], jax.random.PRNGKey(seed), job["sample_input"],
                job["optimizer"],
            )
        )
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated(mesh)),
            shapes,
        )
        manager = CheckpointManager(ckpt_dir)
        try:
            return manager.restore(template)
        finally:
            manager.close()
