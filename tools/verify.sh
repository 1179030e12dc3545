#!/usr/bin/env bash
# One-shot verification gate (referenced from README):
#
#   1. tier-1 pytest            (the driver's command: CPU, not slow, -n 6)
#   2. edl-lint --changed       (static analysis over the working diff)
#   3. edl_report --check       (regression sentinel over the run archive,
#                                only when an archive index exists —
#                                $EDL_RUN_ARCHIVE or ./runs)
#
# Exit 0 only when every armed gate is green. Usage: tools/verify.sh
set -u -o pipefail
cd "$(dirname "$0")/.."

rc=0

# the driver's command (/root/TESTS_LAST_RUN.json "commands"), flag for
# flag: six workers, a file to a worker, 1470 s. The driver also sets
# ALLOW_MULTIPLE_LIBTPU_LOAD=1 for its own runs on the CPU; it is passed
# through when the caller sets it and never set here (on a TPU host that
# lock is what keeps two processes off one chip).
echo "== tier-1 pytest" >&2
if ! timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p xdist -n 6 --dist loadfile -p no:randomly; then
  echo "== tier-1 pytest RED" >&2
  rc=1
fi

echo "== edl-lint --changed" >&2
if ! JAX_PLATFORMS=cpu python -m tools.edl_lint --changed --compact; then
  echo "== edl-lint RED" >&2
  rc=1
fi

# consistency soak: seeded failover drills whose taped op histories
# replay through the history checker (no stale reads, monotonic
# sessions, gap-free watches); verdicts land in the run archive
# (EDL_RUN_ARCHIVE or the chaos workdir's runs/). chaos_run exits
# nonzero on any red invariant.
echo "== store consistency soak (store-failover,store-shard-failover x5)" >&2
if ! timeout -k 10 900 env JAX_PLATFORMS=cpu python tools/chaos_run.py \
    --scenario store-failover,store-shard-failover --repeat 5 \
    >/dev/null; then
  echo "== store consistency soak RED" >&2
  rc=1
fi

# EDL_RUN_ARCHIVE sentinels (archive.py's env contract): 0 = archiving
# disabled, 1 = "the default root" — both resolve like the producers do
runs="${EDL_RUN_ARCHIVE:-runs}"
if [ "$runs" = "1" ]; then
  runs="runs"
fi
if [ "$runs" != "0" ] && [ -f "$runs/index.jsonl" ]; then
  echo "== edl_report --check ($runs)" >&2
  if ! JAX_PLATFORMS=cpu python -m tools.edl_report --runs "$runs" --check; then
    echo "== edl_report RED (a table metric regressed vs its rolling baseline)" >&2
    rc=1
  fi
else
  echo "== edl_report skipped: no archive index at $runs/index.jsonl" >&2
fi

exit $rc
