"""Run a list of benchmark runs one after another, each a process of its own
(this parent never imports jax, so it never holds the chip), and gather
their last lines. It measures as the driver does: several runs of a cell,
each with another seed, and for every metric the spread (distance between
the quartiles over the median).

    python3 benchmark/tools/measure.py --tag first \
        --runs resnet50_vd.steady:1:0,resnet50_vd.steady:2:0,resnet50_vd.steady:3:1

A run is ``cell:seed:trace``. Logs and the gathered lines go to
``chiprun_out/bench/<tag>/`` (small; they come back from the chip tool).
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values):
    """Distance between the quartiles over the median."""
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4, method="inclusive")
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else None


def summarize(rows):
    table = {}
    for row in rows:
        if row.get("line") is None:
            continue
        key = (row["cell"], row["trace"], row.get("set", 0))
        for name, m in row["line"]["metrics"].items():
            table.setdefault(key, {}).setdefault(name, []).append(m["value"])
        for name, m in row["line"].get("unjudged", {}).items():
            table.setdefault(key, {}).setdefault(name + "(unjudged)", []).append(m["value"])
    out = []
    for (cell, trace, which), metrics in sorted(table.items()):
        for name, values in metrics.items():
            out.append({
                "cell": cell, "trace": trace, "set": which, "metric": name,
                "n": len(values), "median": statistics.median(values),
                "spread": spread(values), "values": values,
            })
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tag", required=True)
    parser.add_argument("--runs", required=True,
                        help="comma-separated cell:seed:trace[:set]")
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--dump-trace", action="store_true",
                        help="keep each traced run's trace and print its planes")
    parser.add_argument("--cwd", default=ROOT,
                        help="the checkout to run in (default: this one)")
    args = parser.parse_args(argv)

    out_dir = os.path.join(ROOT, "chiprun_out", "bench", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for i, spec in enumerate(args.runs.split(",")):
        parts = spec.split(":")
        cell, seed, trace = parts[0], int(parts[1]), int(parts[2])
        which = int(parts[3]) if len(parts) > 3 else 0
        cmd = [sys.executable, os.path.join("benchmark", "run.py"),
               "--workload", cell, "--seed", str(seed), "--trace", str(trace)]
        if args.seconds is not None:
            cmd += ["--seconds", str(args.seconds)]
        if args.dump_trace and trace:
            cmd.append("--keep-trace")
        log = os.path.join(out_dir, "%02d.%s.%d.t%d.log" % (i, cell, seed, trace))
        t0 = time.monotonic()
        with open(log, "w") as f:
            proc = subprocess.run(cmd, cwd=args.cwd, stdout=subprocess.PIPE,
                                  stderr=f, text=True)
        wall = time.monotonic() - t0
        lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
        line = None
        if proc.returncode == 0 and lines:
            try:
                line = json.loads(lines[-1])
            except ValueError:
                pass
        with open(log, "a") as f:
            f.write("\n".join(lines[-40:]) + "\n")
        row = {"cell": cell, "seed": seed, "trace": trace, "set": which,
               "rc": proc.returncode, "wall_s": wall, "line": line}
        if len(lines) > 1 and lines[-2].startswith('{"detail"'):
            row["detail"] = json.loads(lines[-2])["detail"]
        rows.append(row)
        print(json.dumps(row), flush=True)
        if proc.returncode != 0:
            with open(log) as f:
                print(f.read()[-3000:], flush=True)
        trace_dir = os.path.join(args.cwd, ".scratch", "benchmark", cell, "trace")
        if args.dump_trace and trace and os.path.isdir(trace_dir):
            dump = subprocess.run(
                [sys.executable, os.path.join("benchmark", "reduce_trace.py"),
                 "--dump", trace_dir],
                cwd=args.cwd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True,
            )
            with open(os.path.join(out_dir, "%02d.%s.trace_dump.txt" % (i, cell)), "w") as f:
                f.write(dump.stdout)
            print(dump.stdout[-6000:], flush=True)
        detail = os.path.join(args.cwd, ".scratch", "benchmark", "out",
                              "%s.%d.trace%d.json" % (cell, seed, trace))
        if os.path.exists(detail):
            with open(detail) as src, open(os.path.join(
                out_dir, "%02d.%s" % (i, os.path.basename(detail))), "w") as dst:
                dst.write(src.read())
    with open(os.path.join(out_dir, "rows.jsonl"), "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    summary = summarize(rows)
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    for s in summary:
        print("SUMMARY %-22s t%d set%d %-26s n=%d median=%.6g spread=%s"
              % (s["cell"], s["trace"], s["set"], s["metric"], s["n"], s["median"],
                 "%.4f" % s["spread"] if s["spread"] is not None else "-"),
              flush=True)
    return 0 if all(r["rc"] == 0 and r["line"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
