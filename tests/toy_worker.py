"""Toy training script for launcher tests.

Reports each (stage, rank, world) incarnation by dropping a marker file in
$TEST_OUT_DIR, then either runs until terminated (default) or exits 0 after
$TEST_EXIT_AFTER seconds — standing in for a training script that finishes
its epochs. A real script would resume from checkpoint; this one just
proves the launcher's spawn/kill/respawn/env contract.
"""

import os
import sys
import time

out_dir = os.environ["TEST_OUT_DIR"]
stage = os.environ["EDL_STAGE"]
rank = os.environ["EDL_WORKER_RANK"]
world = os.environ["EDL_NUM_WORKERS"]
coordinator = os.environ["EDL_COORDINATOR"]

marker = os.path.join(out_dir, "run.%s.%s.%s" % (stage, rank, world))
with open(marker, "w") as f:
    f.write(coordinator)

limit = float(os.environ.get("TEST_EXIT_AFTER", "1e9"))
deadline = time.time() + limit
while time.time() < deadline:
    time.sleep(0.05)
sys.exit(0)
