"""The static-analysis plane's own test suite (tier-1, marker: lint).

Covers, per the acceptance criteria:

- red/green fixture snippets for every pass (guarded vs unguarded
  attribute, blocking vs clean event loop, atomic vs torn write, pure
  vs impure jit fn, registered vs rogue env knob),
- annotation grammar (guarded-by / lock-free / event-loop /
  blocking-ok / durability-ok / jit-ok) incl. same-line-only semantics
  for statement annotations,
- baseline add/expire semantics and note preservation,
- the CLI: ``--json`` output shape, ``--list-passes``, unknown
  ``--only``, and the two acceptance directions — the full repo exits
  0 against the committed baseline, and an unguarded mutation injected
  into a copy of ``store/server.py`` exits nonzero.
"""

import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.lint

REPO = pathlib.Path(__file__).resolve().parent.parent

from edl_tpu.analysis import (  # noqa: E402
    build_context,
    collect_env_reads,
    diff_baseline,
    generate_knob_catalogue,
    load_baseline,
    run_analysis,
    write_baseline,
)


def ctx_for(tmp_path, files, design=None):
    """Materialize a fixture tree and build its AnalysisContext."""
    tops = []
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
        top = rel.split("/")[0]
        if top not in tops:
            tops.append(top)
    if design is not None:
        (tmp_path / "DESIGN.md").write_text(design)
    return build_context(tmp_path, tuple(tops))


def run_pass(tmp_path, files, only, design=None):
    findings, _ = run_analysis(
        ctx_for(tmp_path, files, design), only=list(only)
    )
    return findings


# -- lock discipline ----------------------------------------------------------


_LOCK_RED = """
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def start(self):
            threading.Thread(target=self._loop, daemon=True).start()

        def _loop(self):
            self._n += 1

        def poke(self):
            self._n = 5
"""

_LOCK_GREEN = """
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def start(self):
            threading.Thread(target=self._loop, daemon=True).start()

        def _loop(self):
            with self._lock:
                self._n += 1

        def poke(self):
            with self._lock:
                self._n = 5
"""


class TestLockDiscipline:
    def test_unguarded_shared_attr_flags(self, tmp_path):
        found = run_pass(
            tmp_path, {"pkg/w.py": _LOCK_RED}, ["lock-discipline"]
        )
        assert [f.identity for f in found] == ["Worker._n"]
        assert found[0].severity == "warning"
        assert "thread target" in found[0].message

    def test_guarded_attr_is_clean(self, tmp_path):
        assert not run_pass(
            tmp_path, {"pkg/w.py": _LOCK_GREEN}, ["lock-discipline"]
        )

    def test_thread_only_attr_is_clean(self, tmp_path):
        # mutated solely on the thread side: single-writer, no finding
        src = _LOCK_RED.replace("self._n = 5", "pass")
        assert not run_pass(
            tmp_path, {"pkg/w.py": src}, ["lock-discipline"]
        )

    def test_lock_free_annotation_suppresses(self, tmp_path):
        src = _LOCK_RED.replace(
            "self._n = 0",
            "self._n = 0  # edl: lock-free(GIL-atomic counter, test)",
        )
        assert not run_pass(
            tmp_path, {"pkg/w.py": src}, ["lock-discipline"]
        )

    def test_guarded_by_declaration_checks_all_accesses(self, tmp_path):
        src = """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = None  # edl: guarded-by(self._lock)

                def peek(self):
                    return self._q
        """
        found = run_pass(tmp_path, {"pkg/b.py": src}, ["lock-discipline"])
        assert [f.identity for f in found] == ["Box._q"]
        assert found[0].severity == "error"
        assert "guarded-by(self._lock)" in found[0].message

    def test_guarded_by_declaration_green_under_lock(self, tmp_path):
        src = """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = None  # edl: guarded-by(self._lock)

                def peek(self):
                    with self._lock:
                        return self._q
        """
        assert not run_pass(
            tmp_path, {"pkg/b.py": src}, ["lock-discipline"]
        )

    def test_trailing_lock_free_does_not_waive_next_attr(self, tmp_path):
        # a lock-free annotation on _n must not suppress the separate
        # unguarded attr assigned on the following line
        src = _LOCK_RED.replace(
            "self._n += 1",
            "self._n += 1  # edl: lock-free(test)\n            self._m = 1",
        ).replace(
            "self._n = 5",
            "self._n = 5  # edl: lock-free(test)\n            self._m = 2",
        )
        found = run_pass(
            tmp_path, {"pkg/w.py": src}, ["lock-discipline"]
        )
        assert [f.identity for f in found] == ["Worker._m"]

    def test_trailing_annotation_does_not_leak_to_next_line(self, tmp_path):
        # the Monitor._series_writer regression: an annotation trailing
        # line N must not attach to the assignment on line N+1
        src = """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._a = None  # edl: guarded-by(self._lock)
                    self._b = None

                def touch(self):
                    with self._lock:
                        self._a = 1
                    self._b = 2
        """
        assert not run_pass(
            tmp_path, {"pkg/b.py": src}, ["lock-discipline"]
        )


# -- blocking calls -----------------------------------------------------------


_BLOCK_TREE = """
    import hashlib
    import time

    def loop():  # edl: event-loop(test loop)
        tick()

    def tick():
        hashlib.sha256(b"payload").hexdigest()
"""


class TestBlockingCall:
    def test_hash_reachable_from_event_loop_flags(self, tmp_path):
        found = run_pass(
            tmp_path, {"pkg/l.py": _BLOCK_TREE}, ["blocking-call"]
        )
        assert len(found) == 1
        assert "hashlib.sha256" in found[0].message
        assert "pkg.l.loop -> pkg.l.tick" in found[0].message

    def test_blocking_ok_on_line_suppresses(self, tmp_path):
        src = _BLOCK_TREE.replace(
            'hashlib.sha256(b"payload").hexdigest()',
            'hashlib.sha256(b"payload").hexdigest()'
            "  # edl: blocking-ok(tiny constant input)",
        )
        assert not run_pass(
            tmp_path, {"pkg/l.py": src}, ["blocking-call"]
        )

    def test_blocking_ok_on_def_stops_traversal(self, tmp_path):
        src = _BLOCK_TREE.replace(
            "def tick():",
            "def tick():  # edl: blocking-ok(owns its own budget)",
        )
        assert not run_pass(
            tmp_path, {"pkg/l.py": src}, ["blocking-call"]
        )

    def test_unannotated_function_is_not_a_root(self, tmp_path):
        src = _BLOCK_TREE.replace("  # edl: event-loop(test loop)", "")
        assert not run_pass(
            tmp_path, {"pkg/l.py": src}, ["blocking-call"]
        )

    @pytest.mark.parametrize(
        "sleep,expect",
        [
            ("time.sleep(0.1)", 0),      # short tick: fine
            ("time.sleep(5)", 1),        # long literal
            ("time.sleep(backoff)", 1),  # unbounded
        ],
    )
    def test_sleep_thresholds(self, tmp_path, sleep, expect):
        src = """
            import time

            def loop(backoff):  # edl: event-loop(t)
                %s
        """ % sleep
        found = run_pass(tmp_path, {"pkg/s.py": src}, ["blocking-call"])
        assert len(found) == expect

    def test_closure_handed_to_thread_is_not_charged(self, tmp_path):
        src = """
            import threading
            import time

            def loop():  # edl: event-loop(t)
                def side():
                    time.sleep(30)
                threading.Thread(target=side, daemon=True).start()
        """
        assert not run_pass(
            tmp_path, {"pkg/c.py": src}, ["blocking-call"]
        )

    def test_walk_crosses_self_attribute_types(self, tmp_path):
        # launcher._loop -> self.helper.refresh() -> sha256: the PR-8
        # shape, resolved through the __init__ attr-type map
        src = """
            import hashlib

            class Helper:
                def refresh(self):
                    return hashlib.sha256(b"manifest").hexdigest()

            class Boss:
                def __init__(self):
                    self.helper = Helper()

                def loop(self):  # edl: event-loop(supervision)
                    self.helper.refresh()
        """
        found = run_pass(tmp_path, {"pkg/h.py": src}, ["blocking-call"])
        assert len(found) == 1
        assert "Boss.loop -> pkg.h.Helper.refresh" in found[0].message


# -- durability ---------------------------------------------------------------


class TestAtomicWrite:
    def test_in_place_write_flags(self, tmp_path):
        src = """
            def save(path, doc):
                with open(path, "w") as f:
                    f.write(doc)
        """
        found = run_pass(tmp_path, {"store/io.py": src}, ["atomic-write"])
        assert len(found) == 1
        assert found[0].severity == "error"
        assert "torn" in found[0].message

    def test_tmp_fsync_rename_is_clean(self, tmp_path):
        src = """
            import os

            def save(path, doc):
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    f.write(doc)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, path)
        """
        assert not run_pass(
            tmp_path, {"store/io.py": src}, ["atomic-write"]
        )

    def test_rename_without_fsync_warns(self, tmp_path):
        src = """
            import os

            def save(path, doc):
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    f.write(doc)
                os.replace(tmp, path)
        """
        found = run_pass(tmp_path, {"store/io.py": src}, ["atomic-write"])
        assert len(found) == 1
        assert found[0].severity == "warning"
        assert "fsync" in found[0].message

    def test_append_mode_exempt(self, tmp_path):
        src = """
            def journal(path, line):
                with open(path, "a") as f:
                    f.write(line)
        """
        assert not run_pass(
            tmp_path, {"store/wal.py": src}, ["atomic-write"]
        )

    def test_out_of_scope_module_exempt(self, tmp_path):
        src = """
            def scratch(path):
                with open(path, "w") as f:
                    f.write("debug")
        """
        assert not run_pass(
            tmp_path, {"pkg/scratch.py": src}, ["atomic-write"]
        )

    def test_durability_ok_suppresses(self, tmp_path):
        src = """
            def save(path, doc):
                with open(path, "w") as f:  # edl: durability-ok(ephemeral debug dump)
                    f.write(doc)
        """
        assert not run_pass(
            tmp_path, {"store/io.py": src}, ["atomic-write"]
        )

    def test_fsync_in_helper_counts(self, tmp_path):
        src = """
            import os

            def _sync(f):
                f.flush()
                os.fsync(f.fileno())

            def save(path, doc):
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    f.write(doc)
                    _sync(f)
                os.replace(tmp, path)
        """
        assert not run_pass(
            tmp_path, {"store/io.py": src}, ["atomic-write"]
        )


# -- jit purity ---------------------------------------------------------------


class TestJitPurity:
    def test_wall_clock_in_jitted_fn_flags(self, tmp_path):
        src = """
            import time
            import jax

            def step(x):
                return x + time.time()

            stepped = jax.jit(step)
        """
        found = run_pass(tmp_path, {"pkg/j.py": src}, ["jit-purity"])
        assert [f.identity for f in found] == ["step:time"]

    def test_pure_fn_is_clean(self, tmp_path):
        src = """
            import jax

            def step(x):
                return x * 2

            stepped = jax.jit(step)
        """
        assert not run_pass(tmp_path, {"pkg/j.py": src}, ["jit-purity"])

    def test_env_read_and_global_flag(self, tmp_path):
        src = """
            import os
            import jax

            COUNT = 0

            @jax.jit
            def step(x):
                global COUNT
                COUNT += 1
                return x + float(os.environ.get("EDL_SCALE", "1"))
        """
        found = run_pass(tmp_path, {"pkg/j.py": src}, ["jit-purity"])
        kinds = sorted(f.identity for f in found)
        assert kinds == ["step:env", "step:global"]

    def test_lambda_and_randomness(self, tmp_path):
        src = """
            import random
            import jax

            f = jax.jit(lambda x: x * random.random())
        """
        found = run_pass(tmp_path, {"pkg/j.py": src}, ["jit-purity"])
        assert [f.identity for f in found] == ["<lambda>:random"]

    def test_helper_one_level_deep_flags(self, tmp_path):
        src = """
            import time
            import jax

            def noisy(x):
                return x + time.time()

            def step(x):
                return noisy(x)

            stepped = jax.jit(step)
        """
        found = run_pass(tmp_path, {"pkg/j.py": src}, ["jit-purity"])
        assert len(found) == 1
        assert "helper noisy" in found[0].message

    def test_jit_ok_suppresses(self, tmp_path):
        src = """
            import time
            import jax

            def step(x):
                return x + time.time()  # edl: jit-ok(host callback, test)

            stepped = jax.jit(step)
        """
        assert not run_pass(tmp_path, {"pkg/j.py": src}, ["jit-purity"])

    def test_same_named_method_does_not_shadow_module_fn(self, tmp_path):
        # a bare Name at the jit site can never mean a method: the pure
        # module-level step must win over Profiler.step's time.time()
        src = """
            import time
            import jax

            def step(x):
                return x * 2

            class Profiler:
                def step(self):
                    return time.time()

            stepped = jax.jit(step)
        """
        assert not run_pass(tmp_path, {"pkg/j.py": src}, ["jit-purity"])

    def test_factory_local_def_resolves_lexically(self, tmp_path):
        # train/step.py shape: the jit call inside the factory must
        # resolve the factory's LOCAL step (impure here), even with a
        # same-named pure def at module level
        src = """
            import time
            import jax

            def step(x):
                return x * 2

            def make_step():
                def step(x):
                    return x + time.time()
                return jax.jit(step)
        """
        found = run_pass(tmp_path, {"pkg/j.py": src}, ["jit-purity"])
        assert [f.identity for f in found] == ["step:time"]

    def test_unjitted_impure_fn_is_clean(self, tmp_path):
        src = """
            import time

            def wallclock():
                return time.time()
        """
        assert not run_pass(tmp_path, {"pkg/j.py": src}, ["jit-purity"])


# -- donation: step-shaped jits must donate their state -----------------------


class TestDonation:
    def test_undonated_step_shaped_call_flags(self, tmp_path):
        src = """
            import jax

            def step(state, batch):
                return state, 0.0

            stepped = jax.jit(step)
        """
        found = run_pass(tmp_path, {"pkg/d.py": src}, ["donation"])
        assert [f.identity for f in found] == ["step:state"]

    def test_donated_step_is_clean(self, tmp_path):
        src = """
            import jax

            def step(state, batch):
                return state, 0.0

            stepped = jax.jit(step, donate_argnums=(0,))
        """
        assert not run_pass(tmp_path, {"pkg/d.py": src}, ["donation"])

    def test_donation_missing_arg0_still_flags(self, tmp_path):
        src = """
            import jax

            def step(state, batch):
                return state, 0.0

            stepped = jax.jit(step, donate_argnums=(1,))
        """
        found = run_pass(tmp_path, {"pkg/d.py": src}, ["donation"])
        assert len(found) == 1
        assert "does not cover" in found[0].message

    def test_donate_argnames_covering_the_param_is_clean(self, tmp_path):
        src = """
            import jax

            def step(state, batch):
                return state, 0.0

            stepped = jax.jit(step, donate_argnames=("state",))
        """
        assert not run_pass(tmp_path, {"pkg/d.py": src}, ["donation"])

    def test_bare_decorator_form_flags(self, tmp_path):
        src = """
            import jax

            @jax.jit
            def step(params, batch):
                return params
        """
        found = run_pass(tmp_path, {"pkg/d.py": src}, ["donation"])
        assert [f.identity for f in found] == ["step:params"]

    def test_partial_decorator_with_donation_is_clean(self, tmp_path):
        src = """
            from functools import partial

            import jax

            @partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
            def step(state, batch, cfg):
                return state
        """
        assert not run_pass(tmp_path, {"pkg/d.py": src}, ["donation"])

    def test_non_state_first_arg_is_not_step_shaped(self, tmp_path):
        # grad-only math functions take x/w/batch first: donating those
        # is usually wrong, so they are not the pass's business
        src = """
            import jax

            def loss_fn(x, y):
                return ((x - y) ** 2).sum()

            f = jax.jit(loss_fn)
            g = jax.jit(lambda w: w * 2)
        """
        assert not run_pass(tmp_path, {"pkg/d.py": src}, ["donation"])

    def test_non_literal_donation_gets_benefit_of_the_doubt(self, tmp_path):
        # train/step.py shape: donate_argnums computed from a flag
        src = """
            import jax

            def make(donate):
                def step(state, batch):
                    return state
                return jax.jit(step, donate_argnums=(0,) if donate else ())
        """
        assert not run_pass(tmp_path, {"pkg/d.py": src}, ["donation"])

    def test_donate_ok_waiver_suppresses(self, tmp_path):
        src = """
            import jax

            def step(state, batch):
                return 0.0

            # edl: donate-ok(eval step, state re-read every batch)
            stepped = jax.jit(step)
        """
        assert not run_pass(tmp_path, {"pkg/d.py": src}, ["donation"])

    def test_method_self_is_not_the_state(self, tmp_path):
        src = """
            import jax

            class Runner:
                @jax.jit
                def step(self, batch):
                    return batch
        """
        assert not run_pass(tmp_path, {"pkg/d.py": src}, ["donation"])


# -- catalogue: metrics / faults ---------------------------------------------


class TestMetricPasses:
    def test_bad_name_flags(self, tmp_path):
        src = """
            REG.counter("edl_requests", "one component group only")
        """
        found = run_pass(
            tmp_path, {"edl_tpu/m.py": src}, ["metric-naming"]
        )
        assert [f.identity for f in found] == ["metric:edl_requests"]

    def test_good_name_needs_catalogue_row(self, tmp_path):
        src = """
            REG.counter("edl_test_requests_total", "help")
        """
        missing = run_pass(
            tmp_path, {"edl_tpu/m.py": src}, ["metric-catalogue"],
            design="# Catalogue\n(nothing)\n",
        )
        assert [f.identity for f in missing] == [
            "metric:edl_test_requests_total"
        ]
        present = run_pass(
            tmp_path, {"edl_tpu/m.py": src}, ["metric-catalogue"],
            design="| `edl_test_requests_total` | count | help |\n",
        )
        assert not present

    def test_fault_point_catalogue_and_shape(self, tmp_path):
        src = """
            FP = fault_point("Test.Point", "bad shape, uncatalogued")
        """
        found = run_pass(
            tmp_path, {"edl_tpu/f.py": src}, ["fault-catalogue"],
            design="# no rows\n",
        )
        idents = sorted(f.identity for f in found)
        assert idents == ["fault:Test.Point", "shape:Test.Point"]

    def test_test_prefixed_fault_points_skip_catalogue(self, tmp_path):
        src = """
            FP = fault_point("test.only.point", "fixture")
        """
        assert not run_pass(
            tmp_path, {"edl_tpu/f.py": src}, ["fault-catalogue"],
            design="# no rows\n",
        )


# -- catalogue: env registry --------------------------------------------------


def _design_with_block(ctx):
    return "# Knobs\n\n%s\n" % generate_knob_catalogue(ctx)


class TestEnvRegistry:
    def _tree(self, tmp_path, extra=""):
        files = {
            "edl_tpu/a.py": """
                import os

                TTL = os.environ.get("EDL_TEST_TTL", "5")
            """,
        }
        if extra:
            files["edl_tpu/b.py"] = extra
        return files

    def test_registered_knob_is_clean(self, tmp_path):
        files = self._tree(tmp_path)
        ctx = ctx_for(tmp_path, files)
        (tmp_path / "DESIGN.md").write_text(_design_with_block(ctx))
        ctx = ctx_for(tmp_path, files)  # re-read DESIGN
        findings, _ = run_analysis(ctx, only=["env-registry"])
        assert not findings

    def test_rogue_knob_flags_unregistered_and_drift(self, tmp_path):
        files = self._tree(tmp_path)
        ctx = ctx_for(tmp_path, files)
        design = _design_with_block(ctx)
        files["edl_tpu/b.py"] = """
            import os

            NEW = os.environ.get("EDL_TOTALLY_NEW_KNOB")
        """
        ctx = ctx_for(tmp_path, files, design=design)
        findings, _ = run_analysis(ctx, only=["env-registry"])
        idents = sorted(f.identity for f in findings)
        assert idents == ["drift", "unregistered:EDL_TOTALLY_NEW_KNOB"]

    def test_near_miss_typo_detected(self, tmp_path):
        files = self._tree(tmp_path)
        ctx = ctx_for(tmp_path, files)
        design = _design_with_block(ctx)
        files["edl_tpu/b.py"] = """
            import os

            TTL = os.environ.get("EDL_TEST_TTLS", "5")
        """
        ctx = ctx_for(tmp_path, files, design=design)
        findings, _ = run_analysis(ctx, only=["env-registry"])
        typo = [f for f in findings if f.identity.startswith("typo:")]
        assert len(typo) == 1
        assert "EDL_TEST_TTL" in typo[0].message

    def test_conflicting_defaults_flag(self, tmp_path):
        files = self._tree(tmp_path)
        files["edl_tpu/b.py"] = """
            import os

            TTL = os.environ.get("EDL_TEST_TTL", "30")
        """
        ctx = ctx_for(tmp_path, files)
        design = _design_with_block(ctx)
        ctx = ctx_for(tmp_path, files, design=design)
        findings, _ = run_analysis(ctx, only=["env-registry"])
        conflict = [
            f for f in findings if f.identity.startswith("default-conflict:")
        ]
        assert len(conflict) == 1
        assert "'30'" in conflict[0].message and "'5'" in conflict[0].message

    def test_stale_catalogue_row_warns(self, tmp_path):
        files = self._tree(tmp_path)
        ctx = ctx_for(tmp_path, files)
        design = _design_with_block(ctx).replace(
            "<!-- edl-lint:knob-catalogue:end -->",
            "| `EDL_GONE_KNOB` | `'x'` | nothing |\n"
            "<!-- edl-lint:knob-catalogue:end -->",
        )
        ctx = ctx_for(tmp_path, files, design=design)
        findings, _ = run_analysis(ctx, only=["env-registry"])
        idents = sorted(f.identity for f in findings)
        assert "stale:EDL_GONE_KNOB" in idents and "drift" in idents

    def test_narrowed_scope_skips_stale_and_drift(self, tmp_path):
        # analyzing a subtree must not conclude knobs read elsewhere
        # are stale or that the full-scope table drifted
        files = {
            "edl_tpu/a.py": 'import os\nX = os.environ.get("EDL_NS_A", "1")\n',
            "edl_tpu/sub/b.py":
                'import os\nY = os.environ.get("EDL_NS_B", "2")\n',
        }
        ctx = ctx_for(tmp_path, files)
        (tmp_path / "DESIGN.md").write_text(_design_with_block(ctx))
        narrowed = build_context(tmp_path, ("edl_tpu/sub",))
        findings, _ = run_analysis(narrowed, only=["env-registry"])
        assert not findings, [str(f) for f in findings]
        # the full-scope run still performs both checks
        full = build_context(tmp_path, ("edl_tpu",))
        findings, _ = run_analysis(full, only=["env-registry"])
        assert not findings

    def test_collect_env_reads_sees_every_shape(self, tmp_path):
        src = """
            import os

            A = os.environ.get("EDL_SHAPE_A", "1")
            B = os.environ["EDL_SHAPE_B"]
            C = os.getenv("EDL_SHAPE_C")
            D = "EDL_SHAPE_D" in os.environ
            os.environ["EDL_NOT_A_READ"] = "write"
        """
        ctx = ctx_for(tmp_path, {"edl_tpu/e.py": src})
        reads = collect_env_reads(ctx)
        assert sorted(reads) == [
            "EDL_SHAPE_A", "EDL_SHAPE_B", "EDL_SHAPE_C", "EDL_SHAPE_D"
        ]


# -- lock order (interprocedural) ---------------------------------------------


_ABBA_RED = """
    import threading

    class Pair:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def forward(self):
            with self._a:
                with self._b:
                    pass

        def backward(self):
            with self._b:
                with self._a:
                    pass
"""

_ABBA_GREEN = _ABBA_RED.replace(
    "with self._b:\n                with self._a:",
    "with self._a:\n                with self._b:",
)


class TestLockOrder:
    def test_ab_ba_cycle_flags(self, tmp_path):
        found = run_pass(tmp_path, {"pkg/p.py": _ABBA_RED}, ["lock-order"])
        assert len(found) == 1
        f = found[0]
        assert f.severity == "error"
        assert "inconsistent acquisition order" in f.message
        assert f.identity == "cycle:pkg.p.Pair._a+pkg.p.Pair._b"

    def test_consistent_order_is_clean(self, tmp_path):
        # both paths acquire A then B: edges agree, no cycle
        assert not run_pass(
            tmp_path, {"pkg/p.py": _ABBA_GREEN}, ["lock-order"]
        )

    def test_interprocedural_cycle_across_helpers(self, tmp_path):
        # the inner acquisition hides one call hop away in each
        # direction — only a call-graph-propagated lock-set sees it
        src = """
            import threading

            class Pair:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def _take_a(self):
                    with self._a:
                        pass

                def _take_b(self):
                    with self._b:
                        pass

                def forward(self):
                    with self._a:
                        self._take_b()

                def backward(self):
                    with self._b:
                        self._take_a()
        """
        found = run_pass(tmp_path, {"pkg/p.py": src}, ["lock-order"])
        assert len(found) == 1
        assert "Pair.forward -> " in found[0].message

    def test_lock_order_ok_waives_edge(self, tmp_path):
        src = _ABBA_RED.replace(
            "with self._b:\n                with self._a:",
            "with self._b:\n                with self._a:"
            "  # edl: lock-order-ok(shutdown-only path, test)",
        )
        assert not run_pass(tmp_path, {"pkg/p.py": src}, ["lock-order"])

    def test_three_lock_cycle(self, tmp_path):
        src = """
            import threading

            class Trio:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                    self._c = threading.Lock()

                def ab(self):
                    with self._a:
                        with self._b:
                            pass

                def bc(self):
                    with self._b:
                        with self._c:
                            pass

                def ca(self):
                    with self._c:
                        with self._a:
                            pass
        """
        found = run_pass(tmp_path, {"pkg/t.py": src}, ["lock-order"])
        assert len(found) == 1
        assert "cycle" in found[0].message
        assert found[0].identity.startswith("cycle:")

    def test_reacquire_plain_lock_flags_rlock_clean(self, tmp_path):
        src = """
            import threading

            class Box:
                def __init__(self):
                    self._mu = threading.%s()

                def outer(self):
                    with self._mu:
                        self.inner()

                def inner(self):
                    with self._mu:
                        pass
        """
        found = run_pass(
            tmp_path, {"pkg/b.py": src % "Lock"}, ["lock-order"]
        )
        assert [f.identity for f in found] == ["reacquire:pkg.b.Box._mu"]
        assert not run_pass(
            tmp_path, {"pkg/b.py": src % "RLock"}, ["lock-order"]
        )

    def test_explicit_acquire_release_region_tracked(self, tmp_path):
        # the PR-12 replicator idiom: acquire(timeout)/try/finally
        src = """
            import threading

            class Pair:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def forward(self):
                    self._a.acquire()
                    try:
                        with self._b:
                            pass
                    finally:
                        self._a.release()

                def backward(self):
                    with self._b:
                        with self._a:
                            pass
        """
        found = run_pass(tmp_path, {"pkg/p.py": src}, ["lock-order"])
        assert len(found) == 1
        assert "inconsistent acquisition order" in found[0].message

    def test_module_level_locks_participate(self, tmp_path):
        src = """
            import threading

            _REG = threading.Lock()

            class Box:
                def __init__(self):
                    self._mu = threading.Lock()

                def one(self):
                    with self._mu:
                        with _REG:
                            pass

                def two(self):
                    with _REG:
                        with self._mu:
                            pass
        """
        found = run_pass(tmp_path, {"pkg/m.py": src}, ["lock-order"])
        assert len(found) == 1
        assert "pkg.m._REG" in found[0].message


# -- blocking under lock (interprocedural) ------------------------------------


_DIAL_UNDER_LOCK = """
    import socket
    import threading

    class Warm:
        def __init__(self):
            self._mu = threading.Lock()

        def note(self):
            with self._mu:
                self._helper()

        def _helper(self):
            socket.create_connection(("127.0.0.1", 1), timeout=10)
"""


class TestBlockingUnderLock:
    def test_helper_hop_dial_under_lock_flags(self, tmp_path):
        # the PR-9 warm/aot bug shape: the lock and the dial live in
        # different functions
        found = run_pass(
            tmp_path, {"pkg/w.py": _DIAL_UNDER_LOCK},
            ["blocking-under-lock"],
        )
        assert len(found) == 1
        f = found[0]
        assert f.severity == "error"
        assert "socket dial" in f.message
        assert "Warm._mu" in f.message
        assert "Warm.note -> pkg.w.Warm._helper" in f.message
        # the finding anchors the offending call, not the lock site
        assert f.path == "pkg/w.py"

    def test_dial_outside_lock_is_clean(self, tmp_path):
        src = _DIAL_UNDER_LOCK.replace(
            "with self._mu:\n                self._helper()",
            "with self._mu:\n                pass\n"
            "            self._helper()",
        )
        assert not run_pass(
            tmp_path, {"pkg/w.py": src}, ["blocking-under-lock"]
        )

    def test_blocking_ok_on_call_line_waives(self, tmp_path):
        src = _DIAL_UNDER_LOCK.replace(
            'socket.create_connection(("127.0.0.1", 1), timeout=10)',
            'socket.create_connection(("127.0.0.1", 1), timeout=10)'
            "  # edl: blocking-ok(bounded, test)",
        )
        assert not run_pass(
            tmp_path, {"pkg/w.py": src}, ["blocking-under-lock"]
        )

    def test_blocking_ok_on_def_stops_traversal(self, tmp_path):
        src = _DIAL_UNDER_LOCK.replace(
            "def _helper(self):",
            "def _helper(self):  # edl: blocking-ok(owns its budget)",
        )
        assert not run_pass(
            tmp_path, {"pkg/w.py": src}, ["blocking-under-lock"]
        )

    def test_unbounded_join_and_wait_flag_bounded_clean(self, tmp_path):
        src = """
            import threading

            class Box:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._t = threading.Thread(target=self._run)
                    self._done = threading.Event()

                def _run(self):
                    pass

                def bad_join(self):
                    with self._mu:
                        self._t.join()%s

                def bad_wait(self):
                    with self._mu:
                        self._done.wait()%s
        """
        found = run_pass(
            tmp_path, {"pkg/b.py": src % ("", "")},
            ["blocking-under-lock"],
        )
        prims = sorted(f.message.split(" while")[0] for f in found)
        assert len(found) == 2
        assert "thread join with no timeout" in prims[0]
        assert "wait() with no timeout" in prims[1]
        # a timeout bounds both: clean
        src_bounded = """
            import threading

            class Box:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._t = threading.Thread(target=self._run)
                    self._done = threading.Event()

                def _run(self):
                    pass

                def ok_join(self):
                    with self._mu:
                        self._t.join(5.0)

                def ok_wait(self):
                    with self._mu:
                        self._done.wait(timeout=5.0)
        """
        assert not run_pass(
            tmp_path, {"pkg/b.py": src_bounded}, ["blocking-under-lock"]
        )

    def test_condition_wait_on_held_lock_exempt(self, tmp_path):
        # cv.wait() RELEASES the held condition: not a stall — unless
        # another lock is still held
        src = """
            import threading

            class Q:
                def __init__(self):
                    self._cv = threading.Condition()

                def pop(self):
                    with self._cv:
                        self._cv.wait()
        """
        assert not run_pass(
            tmp_path, {"pkg/q.py": src}, ["blocking-under-lock"]
        )
        src_two = """
            import threading

            class Q:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._cv = threading.Condition()

                def pop(self):
                    with self._mu:
                        with self._cv:
                            self._cv.wait()
        """
        found = run_pass(
            tmp_path, {"pkg/q.py": src_two}, ["blocking-under-lock"]
        )
        assert len(found) == 1
        assert "Q._mu" in found[0].message

    def test_no_lock_no_finding(self, tmp_path):
        src = """
            import socket

            def dial():
                socket.create_connection(("127.0.0.1", 1))
        """
        assert not run_pass(
            tmp_path, {"pkg/d.py": src}, ["blocking-under-lock"]
        )

    def test_explicit_acquire_region_reaches_helper(self, tmp_path):
        # the PR-12 flush shape: acquire(timeout=...) + try/finally,
        # slow helper inside the region
        src = """
            import socket
            import threading

            class Rep:
                def __init__(self):
                    self._pass_lock = threading.Lock()

                def run(self):
                    self._pass_lock.acquire()
                    try:
                        self._push()
                    finally:
                        self._pass_lock.release()

                def _push(self):
                    socket.create_connection(("127.0.0.1", 1))
        """
        found = run_pass(
            tmp_path, {"pkg/r.py": src}, ["blocking-under-lock"]
        )
        assert len(found) == 1
        assert "Rep._pass_lock" in found[0].message


# -- wire protocol ------------------------------------------------------------


_WIRE_PAIR = {
    "edl_tpu/client.py": """
        class Client:
            def put(self, k, v):
                return self.request("put", k=k, v=v)

            def _pump(self, frame):
                if "w" in frame:
                    return frame["ev"]
    """,
    "edl_tpu/server.py": """
        class Server:
            def _op_put(self, conn, req):
                return {}

            def _fanout(self, conn, wid, evs):
                self._send(conn, {"w": wid, "ev": evs})

            def _send(self, conn, payload):
                pass
    """,
}


class TestWireProtocol:
    def test_matched_ops_and_frames_clean(self, tmp_path):
        assert not run_pass(tmp_path, dict(_WIRE_PAIR), ["wire-protocol"])

    def test_client_op_without_handler_flags(self, tmp_path):
        files = dict(_WIRE_PAIR)
        files["edl_tpu/client.py"] = files["edl_tpu/client.py"].replace(
            'self.request("put", k=k, v=v)',
            'self.request("frobnicate", k=k, v=v)',
        )
        found = run_pass(tmp_path, files, ["wire-protocol"])
        idents = sorted(f.identity for f in found)
        assert "unhandled:frobnicate" in idents
        assert "unsent:put" in idents  # the orphaned handler warns too
        unhandled = [f for f in found if f.identity.startswith("unhandled")]
        assert unhandled[0].severity == "error"

    def test_handled_but_unsent_warns_and_waives(self, tmp_path):
        files = dict(_WIRE_PAIR)
        files["edl_tpu/server.py"] = files["edl_tpu/server.py"].replace(
            "def _op_put(self, conn, req):",
            "def _op_put(self, conn, req):\n"
            "                return {}\n\n"
            "            def _op_native_only(self, conn, req):",
        )
        found = run_pass(tmp_path, files, ["wire-protocol"])
        assert [f.identity for f in found] == ["unsent:native_only"]
        assert found[0].severity == "warning"
        files["edl_tpu/server.py"] = files["edl_tpu/server.py"].replace(
            "def _op_native_only(self, conn, req):",
            "def _op_native_only(self, conn, req):"
            "  # edl: protocol-ok(native twin sends it, test)",
        )
        assert not run_pass(tmp_path, files, ["wire-protocol"])

    def test_server_frame_without_decoder_flags(self, tmp_path):
        files = dict(_WIRE_PAIR)
        files["edl_tpu/server.py"] = files["edl_tpu/server.py"].replace(
            '{"w": wid, "ev": evs}', '{"zz": wid, "ev": evs}'
        )
        found = run_pass(tmp_path, files, ["wire-protocol"])
        idents = [f.identity for f in found]
        assert idents == ["frame-undecoded:zz"]
        assert found[0].severity == "error"

    def test_method_compare_dispatch_counts_as_handler(self, tmp_path):
        files = dict(_WIRE_PAIR)
        files["edl_tpu/server.py"] = """
            class Server:
                def serve(self, req):
                    method = req.get("m")
                    if method == "put":
                        return {}

                def _fanout(self, conn, wid, evs):
                    self._send(conn, {"w": wid, "ev": evs})

                def _send(self, conn, payload):
                    pass
        """
        assert not run_pass(tmp_path, files, ["wire-protocol"])

    def test_methods_table_counts_as_handler(self, tmp_path):
        files = dict(_WIRE_PAIR)
        files["edl_tpu/server.py"] = """
            class Server:
                _METHODS = {
                    "put": lambda self, req: {},
                }

                def _fanout(self, conn, wid, evs):
                    self._send(conn, {"w": wid, "ev": evs})

                def _send(self, conn, payload):
                    pass
        """
        assert not run_pass(tmp_path, files, ["wire-protocol"])

    def test_intolerant_optional_field_subscript_flags(self, tmp_path):
        files = dict(_WIRE_PAIR)
        files["edl_tpu/client.py"] = files["edl_tpu/client.py"].replace(
            'return self.request("put", k=k, v=v)',
            'resp = self.request("put", k=k, v=v)\n'
            '                return resp["e"]',
        )
        found = run_pass(tmp_path, files, ["wire-protocol"])
        assert len(found) == 1
        f = found[0]
        assert f.identity == "intolerant:e:edl_tpu.client"
        assert ".get('e')" in f.message
        # .get is the tolerant decode: clean
        files["edl_tpu/client.py"] = files["edl_tpu/client.py"].replace(
            'return resp["e"]', 'return resp.get("e")'
        )
        assert not run_pass(tmp_path, files, ["wire-protocol"])

    def test_catalogue_drift_and_rows(self, tmp_path):
        from edl_tpu.analysis.protocol import generate_wire_catalogue

        ctx = ctx_for(tmp_path, dict(_WIRE_PAIR))
        design = "# Wire\n\n%s\n" % generate_wire_catalogue(ctx)
        # in-sync catalogue: clean
        ctx = ctx_for(tmp_path, dict(_WIRE_PAIR), design=design)
        findings, _ = run_analysis(ctx, only=["wire-protocol"])
        assert not findings, [str(f) for f in findings]
        # a new op appears in code only: uncatalogued + drift
        files = dict(_WIRE_PAIR)
        files["edl_tpu/client.py"] += (
            "\n        def touch(self):\n"
            '            return self.request("put2")\n'
        )
        files["edl_tpu/server.py"] += (
            "\n            def _op_put2(self, conn, req):\n"
            "                return {}\n"
        )
        ctx = ctx_for(tmp_path, files, design=design)
        findings, _ = run_analysis(ctx, only=["wire-protocol"])
        idents = sorted(f.identity for f in findings)
        assert idents == ["drift", "uncatalogued:put2"]
        # a row whose op is gone: stale-row + drift
        stale_design = design.replace(
            "| `put` | rpc |",
            "| `gone_op` | rpc | x | x |\n| `put` | rpc |",
        )
        ctx = ctx_for(tmp_path, dict(_WIRE_PAIR), design=stale_design)
        findings, _ = run_analysis(ctx, only=["wire-protocol"])
        idents = sorted(f.identity for f in findings)
        assert idents == ["drift", "stale-row:gone_op"]

    def test_repo_wire_catalogue_is_current(self):
        """DESIGN.md's committed wire table matches the code (the drift
        check the pass enforces, asserted directly so a failure names
        the regeneration command)."""
        from edl_tpu.analysis import repo_context
        from edl_tpu.analysis.protocol import (
            extract_wire_block, generate_wire_catalogue,
        )

        ctx = repo_context()
        block = extract_wire_block(ctx.design_text)
        assert block is not None, "DESIGN.md lost its wire markers"
        assert block.strip() == generate_wire_catalogue(ctx).strip(), (
            "wire catalogue drifted: run "
            "python -m tools.edl_lint --write-protocol-catalogue"
        )


# -- repo conformance (tier-1 thin wrappers over the new passes) --------------


class TestRepoConformance:
    """Same thin-wrapper pattern as the catalogue lints in test_obs/
    test_chaos/test_monitor: the interprocedural + protocol passes run
    over the shared repo_context() so tier-1 fails on a new finding
    even without invoking the CLI."""

    @pytest.mark.parametrize(
        "pass_name",
        ["lock-order", "blocking-under-lock", "wire-protocol"],
    )
    def test_repo_pass_clean(self, pass_name):
        from edl_tpu.analysis import repo_context, run_analysis

        baseline = json.loads(
            (REPO / ".edl_lint_baseline.json").read_text()
        )["entries"]
        findings, _ = run_analysis(repo_context(), only=[pass_name])
        new = [f for f in findings if f.key not in baseline]
        assert not new, [str(f) for f in new]

    def test_full_repo_all_passes_under_budget(self):
        """ISSUE-14 satellite: ASTs + symbol table + lock-flow are
        cached on the shared context, and a full 13-pass run stays
        under 8s of this process's CPU (not of the wall clock, which
        five busy neighbours under ``-n 6`` stretch)."""
        import time as _time

        from edl_tpu.analysis import repo_context, run_analysis

        ctx = repo_context()
        t0 = _time.process_time()
        _, counts = run_analysis(ctx)
        elapsed = _time.process_time() - t0
        assert len(counts) == 13
        assert elapsed < 8.0, "full 13-pass run took %.1fs of CPU" % elapsed
        # the cross-pass memos actually landed on the shared cache
        assert "symbol_table" in ctx.cache
        assert "lock_flow" in ctx.cache
        assert "protocol_facts" in ctx.cache

    def test_kernels_and_models_read_no_environment(self):
        """What runs on the chip follows from the call's shapes: a switch
        under ops/ or models/ is a PR the benchmark measures in every
        cell, never a variable read at trace time."""
        import ast

        from edl_tpu.analysis import repo_context

        chip_path = ("edl_tpu/ops/", "edl_tpu/models/")
        mods = [
            m for m in repo_context().modules
            if m.relpath.startswith(chip_path) and m.tree is not None
        ]
        assert len(mods) > 10
        # any name, not only the literal EDL_* ones the knob catalogue sees
        reads = [
            "%s:%d" % (m.relpath, node.lineno)
            for m in mods
            for node in ast.walk(m.tree)
            if isinstance(node, ast.Attribute)
            and node.attr in ("environ", "getenv")
        ]
        assert not reads, reads

    def test_attention_takes_no_argument_that_names_an_implementation(self):
        """``ops/attention.py`` holds one kernel family: which kernels a call
        runs follows from its shapes, so no function there has a parameter
        through which a second family could be chosen."""
        import ast

        from edl_tpu.analysis import repo_context

        (mod,) = [
            m for m in repo_context().modules
            if m.relpath == "edl_tpu/ops/attention.py"
        ]
        functions = [
            node for node in ast.walk(mod.tree)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda))
        ]
        assert len(functions) > 40
        named = [
            "%s:%d %s" % (mod.relpath, node.lineno, arg.arg)
            for node in functions
            for arg in (
                node.args.posonlyargs + node.args.args + node.args.kwonlyargs
                + [a for a in (node.args.vararg, node.args.kwarg) if a]
            )
            if arg.arg in ("impl", "implementation") or arg.arg.endswith("_impl")
        ]
        assert not named, named


# -- baseline semantics -------------------------------------------------------


class TestBaseline:
    def test_add_expire_and_note_preservation(self, tmp_path):
        base = tmp_path / "base.json"
        found = run_pass(
            tmp_path, {"pkg/w.py": _LOCK_RED}, ["lock-discipline"]
        )
        assert len(found) == 1
        write_baseline(base, found)
        entries = load_baseline(base)
        assert list(entries) == [found[0].key]

        # annotate the note, then diff: baselined, nothing new
        doc = json.loads(base.read_text())
        doc["entries"][found[0].key] = "tracked: see TICKET-42"
        base.write_text(json.dumps(doc))
        new, old, stale = diff_baseline(found, load_baseline(base))
        assert not new and len(old) == 1 and not stale

        # fix the finding -> the entry is stale; rewrite expires it but
        # keeps notes for entries that persist
        new, old, stale = diff_baseline([], load_baseline(base))
        assert stale == [found[0].key]
        write_baseline(base, found, notes=load_baseline(base))
        assert load_baseline(base)[found[0].key] == "tracked: see TICKET-42"

    def test_new_finding_vs_populated_baseline(self, tmp_path):
        base = tmp_path / "base.json"
        found = run_pass(
            tmp_path, {"pkg/w.py": _LOCK_RED}, ["lock-discipline"]
        )
        write_baseline(base, found)
        # a second unguarded shared attr appears: _n stays baselined,
        # _m is new (indentation matches the raw fixture pre-dedent)
        grown = _LOCK_RED.replace(
            "self._n += 1", "self._n += 1\n            self._m = 0"
        ).replace(
            "self._n = 5", "self._n = 5\n            self._m = 9"
        )
        found2 = run_pass(
            tmp_path, {"pkg/w.py": grown}, ["lock-discipline"]
        )
        new, old, stale = diff_baseline(found2, load_baseline(base))
        assert [f.identity for f in old] == ["Worker._n"]
        assert [f.identity for f in new] == ["Worker._m"]
        assert not stale

    def test_baseline_version_mismatch_raises(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"version": 99, "entries": {}}))
        with pytest.raises(ValueError):
            load_baseline(bad)

    def test_finding_keys_are_line_stable(self, tmp_path):
        found = run_pass(
            tmp_path, {"pkg/w.py": _LOCK_RED}, ["lock-discipline"]
        )
        shifted = run_pass(
            tmp_path,
            {"pkg/w.py": _LOCK_RED.replace(
                "import threading",
                "# an unrelated edit shifts every line\n    import threading",
                1,
            )},
            ["lock-discipline"],
        )
        assert found[0].key == shifted[0].key
        assert found[0].line != shifted[0].line


# -- CLI ----------------------------------------------------------------------


def _cli(args, cwd=REPO, timeout=120):
    return subprocess.run(
        [sys.executable, "-m", "tools.edl_lint"] + args,
        capture_output=True, text=True, timeout=timeout, cwd=str(cwd),
    )


class TestCli:
    def test_repo_is_clean_against_committed_baseline(self):
        """THE acceptance check: all 13 passes over edl_tpu/ + tools/,
        exit 0 against the committed baseline, within the 8s budget
        (PR 9's 4s, relaxed for the interprocedural passes) of the CLI
        process's own CPU: its wall clock is the host's business."""
        out = _cli(["--json", "--baseline", ".edl_lint_baseline.json"])
        assert out.returncode == 0, out.stdout + out.stderr
        doc = json.loads(out.stdout)
        assert doc["summary"]["new"] == 0
        assert doc["cpu_seconds"] < 8
        assert len(doc["passes"]) == 13
        names = {p["name"] for p in doc["passes"]}
        assert {
            "lock-discipline", "blocking-call", "atomic-write",
            "jit-purity", "metric-naming", "metric-catalogue",
            "fault-catalogue", "rule-catalogue", "env-registry",
            "lock-order", "blocking-under-lock", "wire-protocol",
            "donation",
        } <= names
        # per-pass one-line summaries
        for p in doc["passes"]:
            assert p["status"] == "pass" and p["new"] == 0
            assert p["line"].startswith("%s: PASS" % p["name"])

    def test_committed_baseline_is_empty(self):
        """ISSUE-14 satellite: the EDL_JOB_ID/EDL_POD_ID default
        conflicts moved into job_identity() call sites, so nothing is
        baselined any more."""
        entries = json.loads(
            (REPO / ".edl_lint_baseline.json").read_text()
        )["entries"]
        assert entries == {}

    def test_injected_regression_exits_nonzero(self, tmp_path):
        """Acceptance, red direction: an unguarded mutation added to
        store/server.py is a NEW finding and fails the run."""
        dst = tmp_path / "edl_tpu" / "store"
        dst.mkdir(parents=True)
        real = (REPO / "edl_tpu" / "store" / "server.py").read_text()
        dst.joinpath("server.py").write_text(real + textwrap.dedent("""

            class _LintRegressionFixture:
                def __init__(self):
                    self._n = 0
                    self._t = threading.Thread(
                        target=self._loop, daemon=True
                    )

                def _loop(self):
                    self._n += 1

                def stop(self):
                    self._n = 0
        """))
        out = _cli([
            "--root", str(tmp_path), "edl_tpu",
            "--only", "lock-discipline",
            "--baseline", str(REPO / ".edl_lint_baseline.json"),
        ])
        assert out.returncode == 1, out.stdout + out.stderr
        assert "_LintRegressionFixture._n" in out.stdout
        assert "NEW" in out.stdout

    def test_injected_lock_inversion_exits_nonzero(self, tmp_path):
        """ISSUE-14 drill: an AB/BA inversion added to a copy of
        store/server.py is a NEW lock-order finding and fails the run
        against the committed baseline."""
        dst = tmp_path / "edl_tpu" / "store"
        dst.mkdir(parents=True)
        real = (REPO / "edl_tpu" / "store" / "server.py").read_text()
        dst.joinpath("server.py").write_text(real + textwrap.dedent("""

            class _LockOrderRegressionFixture:
                def __init__(self):
                    self._fwd = threading.Lock()
                    self._rev = threading.Lock()

                def _forward(self):
                    with self._fwd:
                        with self._rev:
                            pass

                def _backward(self):
                    with self._rev:
                        with self._fwd:
                            pass
        """))
        out = _cli([
            "--root", str(tmp_path), "edl_tpu",
            "--only", "lock-order",
            "--baseline", str(REPO / ".edl_lint_baseline.json"),
        ])
        assert out.returncode == 1, out.stdout + out.stderr
        assert "_LockOrderRegressionFixture._fwd" in out.stdout
        assert "inconsistent acquisition order" in out.stdout
        assert "NEW" in out.stdout

    def test_changed_narrows_to_git_diff(self, tmp_path):
        """--changed: only git-modified files are analyzed (the
        pre-commit fast path), and a clean tree analyzes nothing."""
        (tmp_path / "edl_tpu").mkdir()
        clean = textwrap.dedent(_LOCK_GREEN)
        (tmp_path / "edl_tpu" / "a.py").write_text(clean)
        (tmp_path / "edl_tpu" / "b.py").write_text("X = 1\n")
        git = ["git", "-C", str(tmp_path),
               "-c", "user.email=t@t", "-c", "user.name=t"]
        subprocess.run(git[:3] + ["init", "-q"], check=True)
        subprocess.run(git[:3] + ["add", "-A"], check=True)
        subprocess.run(git + ["commit", "-qm", "seed"], check=True)
        # clean tree: nothing to analyze, exit 0
        out = _cli(["--root", str(tmp_path), "--changed",
                    "--only", "lock-discipline"])
        assert out.returncode == 0, out.stdout + out.stderr
        assert "no changed python files" in out.stdout
        # a regression lands in b.py only: --changed sees exactly it
        (tmp_path / "edl_tpu" / "b.py").write_text(
            textwrap.dedent(_LOCK_RED)
        )
        out = _cli(["--root", str(tmp_path), "--changed", "--json",
                    "--only", "lock-discipline"])
        assert out.returncode == 1, out.stdout + out.stderr
        doc = json.loads(out.stdout)
        assert doc["paths"] == ["edl_tpu/b.py"]
        assert [f["path"] for f in doc["findings"]] == ["edl_tpu/b.py"]

    def test_changed_conflicts_with_paths(self):
        out = _cli(["--changed", "edl_tpu/store"])
        assert out.returncode == 2
        assert "mutually exclusive" in out.stderr

    def test_narrowed_write_baseline_keeps_scope_gated_entries(self, tmp_path):
        """A path-narrowed --write-baseline must not expire cross-file
        conclusions (wire-protocol unhandled/unsent/drift, env-registry
        stale/drift) the narrowed run never re-evaluated — they are
        scope-gated inside their passes."""
        (tmp_path / "edl_tpu").mkdir()
        (tmp_path / "edl_tpu" / "a.py").write_text("X = 1\n")
        (tmp_path / "edl_tpu" / "sub").mkdir()
        (tmp_path / "edl_tpu" / "sub" / "b.py").write_text("Y = 1\n")
        base = tmp_path / "b.json"
        kept = {
            "wire-protocol:DESIGN.md:drift": "accepted drift",
            "wire-protocol:edl_tpu/sub/b.py:unsent:future_op": "native-only",
            "env-registry:DESIGN.md:stale:EDL_GONE": "accepted",
        }
        base.write_text(json.dumps({"version": 1, "entries": dict(kept)}))
        out = _cli(["--root", str(tmp_path), "edl_tpu/sub",
                    "--baseline", str(base), "--write-baseline"])
        assert out.returncode == 0, out.stdout + out.stderr
        entries = json.loads(base.read_text())["entries"]
        for key, note in kept.items():
            assert entries.get(key) == note, (key, entries)
        # ...and a narrowed read-only run does not report them STALE
        out = _cli(["--root", str(tmp_path), "edl_tpu/sub",
                    "--baseline", str(base)])
        assert out.returncode == 0
        assert "STALE" not in out.stdout

    def test_catalogue_rewrite_refuses_narrowed_scope(self):
        """A --changed / path-narrowed context must never regenerate a
        DESIGN.md catalogue: it would silently truncate the committed
        table to the narrowed subset."""
        for flag in ("--write-knob-catalogue", "--write-protocol-catalogue"):
            out = _cli(["edl_tpu/store", flag])
            assert out.returncode == 2, out.stdout + out.stderr
            assert "full default scope" in out.stderr
            out = _cli(["--changed", flag])
            assert out.returncode == 2
            assert "cannot regenerate" in out.stderr

    def test_compact_json_is_single_line_with_pass_lines(self, tmp_path):
        """``--json --compact``: one line of JSON, one pass/fail summary
        line per pass."""
        (tmp_path / "pkg").mkdir()
        (tmp_path / "pkg" / "w.py").write_text(textwrap.dedent(_LOCK_RED))
        out = _cli(["--root", str(tmp_path), "pkg", "--json", "--compact",
                    "--only", "lock-discipline"])
        assert out.returncode == 1
        assert out.stdout.count("\n") == 1
        doc = json.loads(out.stdout)
        assert "findings" not in doc  # compact drops the full list
        assert doc["findings_new"] == [
            "lock-discipline:pkg/w.py:Worker._n"
        ]
        (p,) = doc["passes"]
        assert p["status"] == "fail"
        assert p["line"] == "lock-discipline: FAIL — 1 finding(s), 1 new"

    def test_json_finding_shape(self, tmp_path):
        (tmp_path / "pkg").mkdir()
        (tmp_path / "pkg" / "w.py").write_text(textwrap.dedent(_LOCK_RED))
        out = _cli(["--root", str(tmp_path), "pkg", "--json",
                    "--only", "lock-discipline"])
        assert out.returncode == 1
        doc = json.loads(out.stdout)
        assert doc["version"] == 1
        (f,) = doc["findings"]
        assert f["pass_name"] == "lock-discipline"
        assert f["path"] == "pkg/w.py"
        assert isinstance(f["line"], int) and f["line"] > 0
        assert f["severity"] == "warning"
        assert f["new"] is True
        assert f["key"] == "lock-discipline:pkg/w.py:Worker._n"
        assert doc["summary"] == {
            "total": 1, "new": 1, "baselined": 0,
            "stale_baseline_keys": [],
        }

    def test_write_baseline_roundtrip(self, tmp_path):
        (tmp_path / "pkg").mkdir()
        (tmp_path / "pkg" / "w.py").write_text(textwrap.dedent(_LOCK_RED))
        base = tmp_path / "b.json"
        first = _cli(["--root", str(tmp_path), "pkg",
                      "--baseline", str(base), "--write-baseline"])
        assert first.returncode == 0, first.stdout + first.stderr
        second = _cli(["--root", str(tmp_path), "pkg",
                       "--baseline", str(base)])
        assert second.returncode == 0, second.stdout + second.stderr
        assert "1 baselined" in second.stdout

    def test_write_baseline_with_only_keeps_unchecked_passes(self, tmp_path):
        """--only + --write-baseline must not expire entries belonging
        to passes that did not run (they were never re-checked)."""
        (tmp_path / "pkg").mkdir()
        (tmp_path / "pkg" / "w.py").write_text(textwrap.dedent(_LOCK_RED))
        base = tmp_path / "b.json"
        base.write_text(json.dumps({
            "version": 1,
            "entries": {
                "env-registry:pkg/other.py:unregistered:EDL_X": "tracked",
            },
        }))
        out = _cli(["--root", str(tmp_path), "pkg", "--baseline", str(base),
                    "--only", "lock-discipline", "--write-baseline"])
        assert out.returncode == 0, out.stdout + out.stderr
        entries = json.loads(base.read_text())["entries"]
        assert entries["env-registry:pkg/other.py:unregistered:EDL_X"] == (
            "tracked"
        )
        assert "lock-discipline:pkg/w.py:Worker._n" in entries

    def test_narrowed_paths_do_not_expire_baseline_entries(self):
        """The reviewer-reproduced corruption: a path-narrowed run must
        neither flag the committed entries STALE nor (with
        --write-baseline, not used here) expire findings in files it
        never scanned."""
        out = _cli(["edl_tpu/store",
                    "--baseline", ".edl_lint_baseline.json"])
        assert out.returncode == 0, out.stdout + out.stderr
        assert "STALE" not in out.stdout

    def test_only_does_not_report_unchecked_entries_stale(self, tmp_path):
        (tmp_path / "pkg").mkdir()
        (tmp_path / "pkg" / "w.py").write_text(textwrap.dedent(_LOCK_GREEN))
        base = tmp_path / "b.json"
        base.write_text(json.dumps({
            "version": 1,
            "entries": {"env-registry:pkg/o.py:unregistered:EDL_X": "t"},
        }))
        out = _cli(["--root", str(tmp_path), "pkg", "--baseline", str(base),
                    "--only", "lock-discipline"])
        assert out.returncode == 0
        assert "STALE" not in out.stdout

    def test_list_passes(self):
        out = _cli(["--list-passes"])
        assert out.returncode == 0
        for name in ("lock-discipline", "blocking-call", "atomic-write",
                     "jit-purity", "env-registry"):
            assert name in out.stdout

    def test_unknown_pass_is_usage_error(self):
        out = _cli(["--only", "no-such-pass"])
        assert out.returncode == 2
        assert "no-such-pass" in out.stderr

    def test_missing_path_is_an_error_not_clean(self, tmp_path):
        # a typo'd path analyzing zero files must not read as "clean"
        out = _cli(["--root", str(tmp_path), "no_such_dir"])
        assert out.returncode == 2
        assert "no_such_dir" in out.stderr

    def test_stale_entries_do_not_fail(self, tmp_path):
        (tmp_path / "pkg").mkdir()
        (tmp_path / "pkg" / "w.py").write_text(textwrap.dedent(_LOCK_GREEN))
        base = tmp_path / "b.json"
        base.write_text(json.dumps({
            "version": 1,
            "entries": {"lock-discipline:pkg/w.py:Worker._gone": "old"},
        }))
        out = _cli(["--root", str(tmp_path), "pkg",
                    "--baseline", str(base), "--only", "lock-discipline"])
        assert out.returncode == 0
        assert "STALE" in out.stdout


# -- knob catalogue generation ------------------------------------------------


class TestKnobCatalogue:
    def test_generated_block_is_stable_and_markered(self, tmp_path):
        ctx = ctx_for(tmp_path, {
            "edl_tpu/a.py": 'import os\nX = os.environ.get("EDL_K_A", "1")\n',
        })
        block = generate_knob_catalogue(ctx)
        assert block.startswith("<!-- edl-lint:knob-catalogue:begin -->")
        assert block.rstrip().endswith("<!-- edl-lint:knob-catalogue:end -->")
        assert "| `EDL_K_A` | `'1'` | edl_tpu.a |" in block
        assert block == generate_knob_catalogue(ctx)

    def test_repo_catalogue_is_current(self):
        """DESIGN.md's committed knob table matches the code (the same
        drift check the env-registry pass enforces, asserted directly
        so a failure names the file to regenerate)."""
        from edl_tpu.analysis import repo_context
        from edl_tpu.analysis.catalogue import extract_knob_block

        ctx = repo_context()
        block = extract_knob_block(ctx.design_text)
        assert block is not None, "DESIGN.md lost its knob markers"
        assert block.strip() == generate_knob_catalogue(ctx).strip(), (
            "knob catalogue drifted: run "
            "python -m tools.edl_lint --write-knob-catalogue"
        )
