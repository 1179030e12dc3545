"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips. It trains the cell's
configuration through ``ElasticTrainer.fit`` under the cell's traffic mix,
measures a window of ``--seconds``, checks the result against the plain
reference, and prints one JSON object as the last line of its output.

Everything that belongs to one configuration, one mix or one metric is a
file found by the name ``BENCHMARK.json`` gives it (see README.md); this
file names none of them. With no TPU, with another number of chips than the
cell asks for, or on a device missing from ``peaks.json``, it exits
non-zero and prints no result. ``--rehearse`` (with ``JAX_PLATFORMS=cpu``)
runs the same control flow at the toy sizes of ``rehearsal/`` and prints a
line whose ``metrics`` is empty (the line before it names what the readers
found).
"""

import time

T_START = time.monotonic()  # process start, as near as Python can tell

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
PACKAGE = os.path.basename(HERE)
SCRATCH = os.path.join(ROOT, ".scratch", PACKAGE)
# where the readers of each list of BENCHMARK.json live, one file a metric
READERS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit("%s: no %s named %r" % (PACKAGE, what, name))


class Finder:
    """Finds a cell's files by name in the directories ``BENCHMARK.json``
    lists under ``paths``: a later PR adds a file, never edits one."""

    def __init__(self, benchmark_file):
        self.base = os.path.dirname(os.path.abspath(benchmark_file))
        self.bench = load_json(benchmark_file)
        self.dirs = [os.path.join(self.base, p) for p in self.bench["paths"]]

    def path(self, *parts):
        for d in self.dirs:
            candidate = os.path.join(d, *parts)
            if os.path.exists(candidate):
                return candidate
        raise SystemExit(
            "%s: no %s under %r" % (PACKAGE, os.path.join(*parts), self.bench["paths"])
        )

    def module(self, kind, name):
        path = self.path(kind, name + ".py")
        spec = importlib.util.spec_from_file_location(
            "%s_%s_%s" % (PACKAGE, kind, name), path
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def read_metrics(finder, kind, cell_name, run):
    """``(judged, unjudged)``, each ``{name: {"value", "unit"}}``, from every
    metric's own reader (``<kind>/<name>.py``). A reader returns None where it
    finds nothing to read, and its metric is left out. What a reader finds in
    a cell that ``BENCHMARK.json`` does not list for its metric is printed
    beside the line's metrics, where nothing judges it."""
    judged, unjudged = {}, {}
    for metric in finder.bench[kind]:
        value = finder.module(READERS[kind], metric["name"]).read(run)
        if value is None or not math.isfinite(value):
            continue
        out = judged if applies(metric, cell_name) else unjudged
        out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return judged, unjudged


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="toy sizes on the CPU; prints no metric")
    parser.add_argument("--keep-trace", action="store_true",
                        help="leave the .xplane.pb under .scratch/ for a look by hand")
    parser.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"),
                        help=argparse.SUPPRESS)  # the tests add a throwaway cell
    args = parser.parse_args(argv)

    finder = Finder(args.benchmark)
    bench = finder.bench
    cell = find(bench["workloads"], args.workload, "workload")
    config_entry = find(bench["configs"], cell["config"], "configuration")
    config_file = os.path.join(finder.base, config_entry["file"])
    if args.rehearse:
        config_file = finder.path(
            "rehearsal", "configs", os.path.basename(config_file)
        )
    config = load_json(config_file)
    mix = load_json(finder.path("traffic", cell["traffic"] + ".json"))
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
    chips = cell["chips"]

    # -- the device, or no run ------------------------------------------------
    import jax

    from edl_tpu.cluster.job_env import default_compile_cache_dir
    from edl_tpu.train.context import enable_compilation_cache

    # the program's own cache plane: JAX_COMPILATION_CACHE_DIR where the
    # machine sets it, else the fixed <checkout>/.cache/xla
    enable_compilation_cache(default_compile_cache_dir())
    devices = jax.devices()
    t_devices = time.monotonic()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if args.rehearse:
        if device["platform"] != "cpu":
            raise SystemExit("%s: --rehearse is for JAX_PLATFORMS=cpu" % PACKAGE)
        peaks = None
    else:
        if device["platform"] != "tpu":
            raise SystemExit(
                "%s: jax found platform %r, not a TPU; a cell is never "
                "measured anywhere else" % (PACKAGE, device["platform"])
            )
        table = load_json(HERE, "peaks.json")
        if device["kind"] not in table:
            raise SystemExit(
                "%s: no peaks for device_kind %r in peaks.json"
                % (PACKAGE, device["kind"])
            )
        peaks = table[device["kind"]]
    if len(devices) != chips:
        raise SystemExit(
            "%s: cell %r needs %d chip(s), jax has %d"
            % (PACKAGE, cell["name"], chips, len(devices))
        )

    from benchmark import harness, reduce_trace
    from edl_tpu.obs import trace as obs_trace
    from edl_tpu.train import ElasticTrainer

    # -- set-up: the job, its host batches, the trainer -----------------------
    family = finder.module("families", config["family"])
    global_batch = config["train"]["batch_per_chip"] * chips
    job = family.build(config, global_batch, args.seed)
    pool = family.host_batches(config, global_batch, args.seed)
    work = os.path.join(SCRATCH, cell["name"])
    shutil.rmtree(work, ignore_errors=True)  # an old checkpoint would resume
    os.makedirs(work)
    ckpt_dir = os.path.join(work, "ckpt") if mix["ckpt"] else None
    trace_dir = os.path.join(work, "trace") if args.trace else None
    schedule = harness.Schedule(mix, pool, seconds, trace_dir)
    if mix["steps_per_epoch"] is not None and not mix["ckpt"]:
        raise SystemExit(
            "%s: a mix of whole epochs leaves fit by an exception, so its "
            "state comes back only through a checkpoint: set ckpt" % PACKAGE
        )
    compiles = harness.CompileWatch()
    trainer = ElasticTrainer(
        job["model"], job["optimizer"], job["loss"],
        sample_input=job["sample_input"], mesh_axes=mix["mesh_axes"],
        fsdp=mix["fsdp"], ckpt_dir=ckpt_dir, apply_kwargs=job["apply_kwargs"],
        async_save=mix["async_save"], seed=args.seed,
    )
    save_errors = 0
    state = None
    try:
        state = trainer.fit(
            schedule.data_fn, epochs=schedule.epochs_argument(),
            on_epoch_end=schedule.on_epoch_end,
        )
    except harness.WindowClosed:
        pass
    at_end = harness.snapshot()
    tracer_events = obs_trace.get_tracer().to_events()
    if schedule.t_close is None:
        raise SystemExit("%s: fit ended before the window closed" % PACKAGE)

    # -- after the window, outside every timing: is the result correct? ------
    dispatched = schedule.steps_dispatched()
    checks = {}
    if ckpt_dir is not None:
        # fit has left and its manager has closed (which waits for the save
        # in flight): an acknowledged save must read back
        try:
            restored, status = harness.restore_newest(
                ckpt_dir, job, mix["mesh_axes"], args.seed
            )
            if status is None:
                raise FileNotFoundError("no checkpoint under %s" % ckpt_dir)
            state = restored
            checks["restored_step"] = status.step
        except Exception as exc:  # noqa: BLE001 — a save that does not read back fails the run
            save_errors += 1
            checks["restore_error"] = repr(exc)
    final_step = int(state.step) if state is not None else None
    checks["state_step"] = final_step
    checks["steps_dispatched"] = dispatched
    in_window = compiles.between(schedule.t_open, schedule.t_close)
    first_steps = sum(
        1 for e in tracer_events
        if e.get("name") == "first_step"
        and e.get("args", {}).get("epoch", 0) >= schedule.first_window_epoch
    )
    checks["compiles_in_window"] = in_window + first_steps
    losses = [e["loss"] for e in schedule.window_epochs() if "loss" in e]
    # a loss that is not finite makes the gradients, the parameters and every
    # later loss not finite, so the last loss of each epoch speaks for all
    bad_losses = sum(1 for v in losses if not math.isfinite(v))
    checks["losses"] = [e.get("loss") for e in schedule.epochs]
    if state is not None:
        if chips > 1:
            digests = harness.replica_digests(state.params)
            checks["replica_digests"] = digests
            checks["replicas_agree"] = len(set(digests.values())) == 1
        slim = types.SimpleNamespace(
            params=harness.on_one_device(state.params, devices[0]),
            batch_stats=harness.on_one_device(state.batch_stats, devices[0]),
            apply_fn=state.apply_fn,
        )
        del state  # frees the optimizer state before the float32 reference
        checks["reference"] = family.check(config, slim, args.seed)
        del slim
    correct = bool(
        losses and bad_losses == 0 and save_errors == 0
        and final_step == dispatched
        and checks.get("restored_step", dispatched) == dispatched
        and checks["compiles_in_window"] == 0
        and checks.get("replicas_agree", True)
        and checks.get("reference", {}).get("ok", False)
    )

    # -- the numbers ----------------------------------------------------------
    window_s = schedule.t_close - schedule.t_open
    step_s, step_s_from = schedule.step_seconds()
    reduced = None
    if trace_dir:
        try:
            reduced = reduce_trace.reduce(
                reduce_trace.find_xplane(trace_dir), schedule.trace_window_ns,
                tracer_events + schedule.boundary_events(),
            )
        except reduce_trace.NoDevicePlane:
            if not args.rehearse:  # the CPU's trace has no device plane
                raise
        if not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)  # tens of MB a run
    run = types.SimpleNamespace(
        cell=cell, config=config, mix=mix, family=family, chips=chips,
        peaks=peaks, seed=args.seed, items_per_step=job["items_per_step"],
        clocks={"t_start": T_START, "t_devices": t_devices,
                "t_open": schedule.t_open, "t_close": schedule.t_close},
        window_s=window_s, window_steps=schedule.window_steps(), step_s=step_s,
        window_epochs=[e["epoch"] for e in schedule.window_epochs()],
        epochs=schedule.epochs, save_stalls=schedule.save_stalls(),
        at_open=schedule.at_open, at_close=schedule.at_close, at_end=at_end,
        tracer_events=tracer_events, trace=reduced,
    )
    device["memory_peak_bytes"] = at_end["memory_peak_bytes"]
    line = {"correct": correct, "attempted": run.window_steps,
            "failed": bad_losses + save_errors, "metrics": {}, "device": device}
    kind = "per_layer" if args.trace else "end_to_end"
    judged, unjudged = read_metrics(finder, kind, cell["name"], run)
    if not args.rehearse:  # a CPU's number is never printed as a device's
        line["metrics"] = judged
        if unjudged:
            line["unjudged"] = unjudged
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = reduced["breakdown"]
    detail = {
        "cell": cell["name"], "seed": args.seed, "trace": args.trace,
        "seconds": seconds, "window_s": window_s, "window_steps": run.window_steps,
        "step_s": step_s, "step_s_from": step_s_from,
        "epochs": schedule.epochs, "save_stalls_s": run.save_stalls,
        "checks": checks, "goodput_at_close": schedule.at_close["goodput"],
        "goodput_at_open": schedule.at_open["goodput"], "line": line,
    }
    if reduced is not None:
        detail["trace_summary"] = reduced["summary"]
    out_dir = os.path.join(SCRATCH, "out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(
        out_dir, "%s.%d.trace%d.json" % (cell["name"], args.seed, args.trace)
    ), "w") as f:
        json.dump(detail, f, indent=1, default=str)
    print(json.dumps({"detail": {
        "window_s": window_s, "window_steps": run.window_steps,
        "step_s": step_s, "step_s_from": step_s_from,
        "save_stalls": len(run.save_stalls), "checks": checks,
        "judged": sorted(judged), "unjudged": sorted(unjudged),
    }}, default=str), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
