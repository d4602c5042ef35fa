"""eksml-lint v3 (ISSUE 12): thread-topology concurrency analysis.

Covers the two inventories (thread roots for every spawn idiom used
in-tree, locks through import aliasing and the class hierarchy),
per-rule positive/negative/suppression fixtures for ``lock-order`` /
``unlocked-shared-state`` / ``blocking-under-lock`` — including the
held-locks-across-call-edges propagation both deadlock rules depend
on — the ``--json`` chain contract, ``--changed`` scoping, the
real-tree clean pin with an empty baseline, and the ISSUE 12
acceptance probes driven in both directions: the shipped tree exits
0, while a lock-order inversion injected into a copy of
``eksml_tpu/data/loader.py`` exits 1 naming both acquisition chains
at file:line, and an injected unlocked two-root mutation exits 1
naming both roots.  The runtime counterpart (the SAME inversion
wedging two real threads) lives in tests/test_fault_tolerance.py
(``proc-lock-inversion`` chaos rung).
"""

import json
import os
import subprocess
import sys
import textwrap
import threading

from eksml_tpu.analysis import run_lint
from eksml_tpu.analysis.concurrency import (CONCURRENCY_RULES,
                                            LockInventory,
                                            discover_thread_roots)
from eksml_tpu.analysis.engine import iter_python_files, load_modules
from eksml_tpu.analysis.graph import ProjectGraph

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT = os.path.join(REPO, "tools", "eksml_lint.py")


def write_tree(tmp_path, files):
    for rel, src in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
    return tmp_path


def lint_tree(tmp_path, files, rules, targets=None):
    root = write_tree(tmp_path, files)
    return run_lint(targets=targets or sorted(files),
                    repo_root=str(root), rules=rules)


def graph_of(tmp_path, files):
    root = write_tree(tmp_path, files)
    paths, _ = iter_python_files(sorted(files), str(root))
    mods, errs = load_modules(paths, str(root))
    assert not errs, errs
    return ProjectGraph(mods)


def _run_cli(*argv, cwd=REPO):
    return subprocess.run([sys.executable, LINT, *argv],
                          capture_output=True, text=True, cwd=cwd)


# ---------------------------------------------------------------------
# thread-root inventory: every spawn idiom used in-tree
# ---------------------------------------------------------------------

def test_thread_roots_every_spawn_idiom(tmp_path):
    g = graph_of(tmp_path, {
        "mod.py": """
            import atexit
            import signal
            import threading
            from concurrent.futures import ThreadPoolExecutor
            from http.server import BaseHTTPRequestHandler

            def worker():
                pass

            def task(x):
                return x

            def mapped(x):
                return x

            def on_sig(signum, frame):
                pass

            def cleanup():
                pass

            class Svc:
                def _run(self):
                    pass

                def start(self):
                    t = threading.Thread(target=self._run,
                                         name="svc")
                    t.start()

            class Handler(BaseHTTPRequestHandler):
                def do_GET(self):
                    pass

                def helper(self):
                    pass

            def main_thread():
                pool = ThreadPoolExecutor(2)
                pool.submit(task, 1)
                pool.map(mapped, [1, 2])
                threading.Thread(target=worker).start()
                signal.signal(signal.SIGTERM, on_sig)
                atexit.register(cleanup)
            """,
        "eksml_tpu/train.py": """
            def main():
                pass
            """,
    })
    roots = discover_thread_roots(g)
    by_name = {r.fi.qualname: r.kind for r in roots}
    assert by_name["worker"] == "thread"
    assert by_name["Svc._run"] == "thread"
    assert by_name["task"] == "executor"
    assert by_name["mapped"] == "executor"
    assert by_name["Handler.do_GET"] == "handler"
    assert by_name["on_sig"] == "signal"
    assert by_name["cleanup"] == "atexit"
    assert by_name["main"] == "main"
    # non-do_* handler methods and never-spawned functions are not roots
    assert "Handler.helper" not in by_name
    assert "main_thread" not in by_name
    # all main-thread entries share ONE identity; spawned roots don't
    mains = [r for r in roots if r.kind == "main"]
    assert all(r.ident == "main" for r in mains)
    assert not any(r.ident == "main" for r in roots
                   if r.kind != "main")


def test_nested_def_thread_target_is_its_own_root(tmp_path):
    """The loader idiom: a nested ``producer`` def spawned as a
    thread must be a root — and its footprint must NOT fold into the
    enclosing (consumer) function."""
    g = graph_of(tmp_path, {
        "mod.py": """
            import threading

            def batches():
                def producer():
                    pass
                t = threading.Thread(target=producer)
                t.start()
            """,
    })
    roots = discover_thread_roots(g)
    assert {r.fi.qualname for r in roots} == {"batches.producer"}


# ---------------------------------------------------------------------
# lock inventory: aliasing + class hierarchy
# ---------------------------------------------------------------------

def test_lock_inventory_through_aliasing(tmp_path):
    g = graph_of(tmp_path, {
        "mod.py": """
            import threading
            import threading as th
            from threading import Lock, RLock

            _GLOBAL = Lock()

            class A:
                def __init__(self):
                    self._lock = th.RLock()
                    self._cond = threading.Condition()
                    self.not_a_lock = dict()
            """,
    })
    inv = LockInventory(g)
    displays = sorted(l.display for l in inv.locks)
    assert displays == ["A._cond", "A._lock", "mod._GLOBAL"]
    assert all(l.line > 0 for l in inv.locks)


def test_lock_resolution_through_base_class(tmp_path):
    """The registry idiom: ``_Series.__init__`` owns the lock,
    ``Counter.inc`` acquires it — subclass methods must resolve to
    the base's lock, or their mutations would misread as unlocked."""
    r = lint_tree(tmp_path, {
        "mod.py": """
            import threading

            class Base:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.v = 0

            class Counter(Base):
                def inc(self):
                    with self._lock:
                        self.v += 1

            class Gauge(Base):
                def set(self):
                    with self._lock:
                        self.v = 2

            c = Counter()
            g = Gauge()

            def w1():
                c.inc()

            def w2():
                g.set()

            threading.Thread(target=w1).start()
            threading.Thread(target=w2).start()
            """,
    }, rules=["unlocked-shared-state"])
    assert r.findings == []


# ---------------------------------------------------------------------
# lock-order
# ---------------------------------------------------------------------

INVERSION_SRC = """
    import threading

    A = threading.Lock()
    B = threading.Lock()

    def w1():
        with A:
            with B:
                pass

    def w2():
        with B:
            with A:
                pass

    threading.Thread(target=w1).start()
    threading.Thread(target=w2).start()
    """


def test_lock_order_flags_two_thread_inversion(tmp_path):
    r = lint_tree(tmp_path, {"mod.py": INVERSION_SRC},
                  rules=["lock-order"])
    assert len(r.findings) == 1
    f = r.findings[0]
    assert "mod.A" in f.message and "mod.B" in f.message
    # both acquisition chains at file:line (w1's inner acquire is on
    # line 9, w2's on line 14 of the dedented source)
    assert "mod.py:9" in f.message and "mod.py:14" in f.message
    assert f.chain and len(f.chain) >= 2
    names = [c["name"] for c in f.chain]
    assert any("acquire" in n for n in names)


def test_lock_order_consistent_order_is_clean(tmp_path):
    needle = "        with B:\n            with A:"
    assert needle in INVERSION_SRC
    src = INVERSION_SRC.replace(
        needle, "        with A:\n            with B:")
    r = lint_tree(tmp_path, {"mod.py": src}, rules=["lock-order"])
    assert r.findings == []


def test_lock_order_propagates_held_locks_through_calls(tmp_path):
    """A→B where B's acquisition is one call away from A's hold."""
    r = lint_tree(tmp_path, {"mod.py": """
        import threading

        A = threading.Lock()
        B = threading.Lock()

        def helper():
            with B:
                pass

        def w1():
            with A:
                helper()

        def w2():
            with B:
                with A:
                    pass

        threading.Thread(target=w1).start()
        threading.Thread(target=w2).start()
        """}, rules=["lock-order"])
    assert len(r.findings) == 1
    assert "helper" in r.findings[0].message


def test_lock_order_single_main_root_is_not_a_deadlock(tmp_path):
    """Both orders on ONE main thread cannot interleave with
    themselves; only spawned/concurrent roots make a cycle fire."""
    r = lint_tree(tmp_path, {"eksml_tpu/train.py": """
        import threading

        A = threading.Lock()
        B = threading.Lock()

        def one():
            with A:
                with B:
                    pass

        def two():
            with B:
                with A:
                    pass

        def main():
            one()
            two()
        """}, rules=["lock-order"])
    assert r.findings == []


def test_lock_order_three_lock_cycle(tmp_path):
    r = lint_tree(tmp_path, {"mod.py": """
        import threading

        A = threading.Lock()
        B = threading.Lock()
        C = threading.Lock()

        def w1():
            with A:
                with B:
                    pass

        def w2():
            with B:
                with C:
                    pass

        def w3():
            with C:
                with A:
                    pass

        threading.Thread(target=w1).start()
        threading.Thread(target=w2).start()
        threading.Thread(target=w3).start()
        """}, rules=["lock-order"])
    assert len(r.findings) == 1
    assert "cycle" in r.findings[0].message
    assert "mod.C" in r.findings[0].message


def test_lock_order_suppression(tmp_path):
    # the finding anchors at the FIRST edge's second acquisition
    # (w1's inner `with B:`) — the suppression sits there
    needle = "        with A:\n            with B:"
    assert needle in INVERSION_SRC
    src = INVERSION_SRC.replace(
        needle,
        "        with A:\n            # eksml-lint: disable=lock-order"
        "\n            with B:")
    r = lint_tree(tmp_path, {"mod.py": src}, rules=["lock-order"])
    assert r.findings == [] and len(r.suppressed) == 1


def test_lock_order_explicit_acquire_release(tmp_path):
    """``.acquire()``/``.release()`` sites participate like ``with``
    — the region ends at the matching release."""
    r = lint_tree(tmp_path, {"mod.py": """
        import threading

        A = threading.Lock()
        B = threading.Lock()

        def w1():
            A.acquire()
            with B:
                pass
            A.release()

        def w2():
            A.acquire()
            A.release()
            with B:
                with A:
                    pass

        threading.Thread(target=w1).start()
        threading.Thread(target=w2).start()
        """}, rules=["lock-order"])
    # w1: A→B; w2: released before B, so only B→A — inversion
    assert len(r.findings) == 1
    r2 = lint_tree(tmp_path / "two", {"mod.py": """
        import threading

        A = threading.Lock()
        B = threading.Lock()

        def w1():
            A.acquire()
            A.release()
            with B:
                pass

        def w2():
            with B:
                with A:
                    pass

        threading.Thread(target=w1).start()
        threading.Thread(target=w2).start()
        """}, rules=["lock-order"])
    assert r2.findings == []


# ---------------------------------------------------------------------
# unlocked-shared-state
# ---------------------------------------------------------------------

def test_lockset_flags_two_root_unlocked_mutation(tmp_path):
    r = lint_tree(tmp_path, {"mod.py": """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def locked_inc(self):
                with self._lock:
                    self.count += 1

            def unlocked_set(self):
                self.count = 5

            def start(self):
                threading.Thread(target=self.locked_inc).start()
                threading.Thread(target=self.unlocked_set).start()
        """}, rules=["unlocked-shared-state"])
    assert len(r.findings) == 1
    f = r.findings[0]
    assert "W.count" in f.message
    assert "no lock" in f.message
    assert "lockset intersection is empty" in f.message
    # anchored at the bare site so a suppression can sit on it
    assert f.line == 14
    assert f.chain[-1]["name"] == "mutate .count"


def test_lockset_common_lock_is_clean(tmp_path):
    r = lint_tree(tmp_path, {"mod.py": """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def a(self):
                with self._lock:
                    self.count += 1

            def b(self):
                with self._lock:
                    self.count = 0

            def start(self):
                threading.Thread(target=self.a).start()
                threading.Thread(target=self.b).start()
        """}, rules=["unlocked-shared-state"])
    assert r.findings == []


def test_lockset_single_root_and_init_are_exempt(tmp_path):
    """One writer thread needs no lock; constructor chains happen-
    before thread publication."""
    r = lint_tree(tmp_path, {"mod.py": """
        import threading

        class W:
            def __init__(self):
                self.count = 0
                self._setup()

            def _setup(self):
                self.count = 1

            def only_writer(self):
                self.count += 1

            def start(self):
                threading.Thread(target=self.only_writer).start()
                threading.Thread(target=self.reader).start()

            def reader(self):
                return self.count
        """}, rules=["unlocked-shared-state"])
    assert r.findings == []


def test_lockset_same_attr_on_unrelated_classes_is_clean(tmp_path):
    """Same-named fields of unrelated classes are different memory —
    one unlocked writer each must not merge into a fake race."""
    r = lint_tree(tmp_path, {"mod.py": """
        import threading

        class P:
            def run(self):
                self.state = 1

        class Q:
            def run2(self):
                self.state = 2

        threading.Thread(target=P().run).start()

        def spawn():
            q = Q()
            threading.Thread(target=q.run2).start()
        """}, rules=["unlocked-shared-state"])
    assert r.findings == []


def test_lockset_held_through_call_edge(tmp_path):
    """A mutation in a helper called under the lock carries the
    caller's lockset (the ProfileTrigger._reject_locked idiom)."""
    r = lint_tree(tmp_path, {"mod.py": """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def _bump_locked(self):
                self.n += 1

            def a(self):
                with self._lock:
                    self._bump_locked()

            def b(self):
                with self._lock:
                    self._bump_locked()

            def start(self):
                threading.Thread(target=self.a).start()
                threading.Thread(target=self.b).start()
        """}, rules=["unlocked-shared-state"])
    assert r.findings == []


def test_lockset_sees_every_tuple_target_element(tmp_path):
    """`self.a, self.b = …` mutates BOTH attributes — a race on the
    second tuple element must not hide behind the first (the loader's
    own `old, self._proc_pool = …` swap idiom)."""
    r = lint_tree(tmp_path, {"mod.py": """
        import threading

        class W:
            def a(self):
                self.first, self.second = 1, 2

            def b(self):
                self.second = 3

            def start(self):
                threading.Thread(target=self.a).start()
                threading.Thread(target=self.b).start()
        """}, rules=["unlocked-shared-state"])
    assert len(r.findings) == 1, r.findings
    assert "W.second" in r.findings[0].message


def test_lockset_suppression(tmp_path):
    r = lint_tree(tmp_path, {"mod.py": """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self.flag = False

            def a(self):
                with self._lock:
                    self.flag = True

            def b(self):
                # idempotent sticky flag, benign race
                self.flag = True  # eksml-lint: disable=unlocked-shared-state

            def start(self):
                threading.Thread(target=self.a).start()
                threading.Thread(target=self.b).start()
        """}, rules=["unlocked-shared-state"])
    assert r.findings == [] and len(r.suppressed) == 1


# ---------------------------------------------------------------------
# blocking-under-lock
# ---------------------------------------------------------------------

BLOCKING_SRC = """
    import queue
    import threading

    L = threading.Lock()
    q = queue.Queue()

    def consumer():
        with L:
            item = q.get()
        return item

    def other():
        with L:
            pass

    threading.Thread(target=consumer).start()
    threading.Thread(target=other).start()
    """


def test_blocking_under_lock_flags_unbounded_queue_get(tmp_path):
    r = lint_tree(tmp_path, {"mod.py": BLOCKING_SRC},
                  rules=["blocking-under-lock"])
    assert len(r.findings) == 1
    f = r.findings[0]
    assert "q.get() without timeout" in f.message
    assert "mod.L" in f.message
    assert "other" in f.message          # the wedged peer is named
    assert f.chain[-1]["name"].startswith("q.get()")


def test_blocking_under_lock_timeout_is_bounded(tmp_path):
    src = BLOCKING_SRC.replace("q.get()", "q.get(timeout=5.0)")
    r = lint_tree(tmp_path, {"mod.py": src},
                  rules=["blocking-under-lock"])
    assert r.findings == []


def test_blocking_under_lock_block_kwarg_semantics(tmp_path):
    """block=True (and the positional `get(True)` spelling) is the
    DEFAULT unbounded wait and must still flag; only block=False —
    non-blocking — exempts."""
    for spelling in ("q.get(block=True)", "q.get(True)"):
        sub = tmp_path / spelling.replace("(", "_").replace(")", "_") \
            .replace("=", "_")
        r = lint_tree(sub, {"mod.py": BLOCKING_SRC.replace(
            "q.get()", spelling)}, rules=["blocking-under-lock"])
        assert len(r.findings) == 1, (spelling, r.findings)
    for spelling in ("q.get(block=False)", "q.get(False)",
                     "q.get(True, 5.0)"):
        sub = tmp_path / spelling.replace("(", "_").replace(")", "_") \
            .replace("=", "_").replace(",", "_").replace(" ", "")
        r = lint_tree(sub, {"mod.py": BLOCKING_SRC.replace(
            "q.get()", spelling)}, rules=["blocking-under-lock"])
        assert r.findings == [], (spelling, r.findings)


def test_blocking_under_lock_private_lock_is_clean(tmp_path):
    """A lock only ONE root ever takes cannot wedge another root."""
    needle = "        with L:\n            pass"
    assert needle in BLOCKING_SRC
    src = BLOCKING_SRC.replace(needle, "        pass")
    r = lint_tree(tmp_path, {"mod.py": src},
                  rules=["blocking-under-lock"])
    assert r.findings == []


def test_blocking_under_lock_collective_and_join_via_helper(tmp_path):
    """jax collectives and a timeout-less join() count as blocking,
    and the lock can be held one call away from the blocking site."""
    r = lint_tree(tmp_path, {"mod.py": """
        import threading
        from jax.experimental import multihost_utils

        L = threading.Lock()

        def sync_all(x):
            return multihost_utils.process_allgather(x)

        def w1(x, t):
            with L:
                out = sync_all(x)
                t.join()
            return out

        def w2():
            with L:
                pass

        threading.Thread(target=w1).start()
        threading.Thread(target=w2).start()
        """}, rules=["blocking-under-lock"])
    whats = sorted(f.message.split(" at ")[0] for f in r.findings)
    assert len(r.findings) == 2, r.findings
    assert any("process_allgather" in w for w in whats)
    assert any(".join() without timeout" in w for w in whats)
    helper = [f for f in r.findings if "process_allgather" in f.message]
    assert any("sync_all" in c["name"] for c in helper[0].chain)


def test_blocking_under_lock_suppression(tmp_path):
    src = BLOCKING_SRC.replace(
        "        item = q.get()",
        "        item = q.get()  # eksml-lint: disable=blocking-under-lock")
    r = lint_tree(tmp_path, {"mod.py": src},
                  rules=["blocking-under-lock"])
    assert r.findings == [] and len(r.suppressed) == 1


def test_generic_method_names_do_not_unique_fallback(tmp_path):
    """``self._stop.wait()`` must not resolve to a project def named
    ``wait`` on an unrelated class — the false edge would attribute
    one root's whole footprint to another (the first whole-repo run's
    watchdog→CheckpointManager phantom)."""
    r = lint_tree(tmp_path, {"mod.py": """
        import threading

        class Manager:
            def wait(self):
                self.pending = 1

        class Watcher:
            def __init__(self):
                self._stop = threading.Event()

            def _run(self):
                self._stop.wait()

            def start(self):
                threading.Thread(target=self._run).start()

        def other_writer(m):
            m2 = Manager()
            m2.wait()

        threading.Thread(target=other_writer).start()
        """}, rules=["unlocked-shared-state"])
    assert r.findings == []


# ---------------------------------------------------------------------
# --json chain contract + --changed scoping
# ---------------------------------------------------------------------

def test_json_output_carries_chain(tmp_path):
    write_tree(tmp_path, {"mod.py": INVERSION_SRC})
    proc = _run_cli("--rules", "lock-order", "--json",
                    str(tmp_path / "mod.py"))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    (finding,) = payload["findings"]
    chain = finding["chain"]
    assert all(set(c) == {"path", "line", "name"} for c in chain)
    assert any(c["name"].startswith("acquire") for c in chain)


def test_changed_scoping_filters_concurrency_findings(tmp_path):
    """The --changed path-filter applies to the v3 rules exactly like
    every other rule: a finding in an unchanged file stays out of a
    scoped result even though the graph still spans both files."""
    write_tree(tmp_path, {"mod.py": INVERSION_SRC,
                          "other.py": "x = 1\n"})
    r = run_lint(targets=["mod.py", "other.py"],
                 repo_root=str(tmp_path), rules=["lock-order"],
                 only_paths=["other.py"])
    assert r.findings == []
    r2 = run_lint(targets=["mod.py", "other.py"],
                  repo_root=str(tmp_path), rules=["lock-order"],
                  only_paths=["mod.py"])
    assert len(r2.findings) == 1


# ---------------------------------------------------------------------
# ISSUE 12 acceptance, both directions
# ---------------------------------------------------------------------

def test_real_tree_concurrency_rules_clean():
    """Forward direction: the shipped tree exits 0 under all three
    rules with an EMPTY baseline."""
    proc = _run_cli("--rules", ",".join(CONCURRENCY_RULES), "--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []
    assert payload["baselined"] == []


def test_acceptance_lock_inversion_in_loader_copy(tmp_path):
    """Reverse direction 1: an A→B / B→A inversion injected into a
    copy of the real loader ( _note_pool_break takes _sub_lock under
    _pool_lock; _substitute_for takes _pool_lock under _sub_lock )
    exits 1 naming lock-order and BOTH acquisition chains at
    file:line."""
    src = open(os.path.join(REPO, "eksml_tpu", "data",
                            "loader.py")).read()
    needle1 = ("        with self._pool_lock:\n"
               "            first = not self._pool_break_pending")
    assert needle1 in src, "loader.py changed; update this probe"
    inj1 = ("        with self._pool_lock:\n"
            "            with self._sub_lock:\n"
            "                pass\n"
            "            first = not self._pool_break_pending")
    needle2 = ("        with self._sub_lock:\n"
               "            for key, order in cycles:")
    assert needle2 in src, "loader.py changed; update this probe"
    inj2 = ("        with self._sub_lock:\n"
            "            with self._pool_lock:\n"
            "                pass\n"
            "            for key, order in cycles:")
    target = tmp_path / "loader_copy.py"
    target.write_text(src.replace(needle1, inj1).replace(needle2, inj2))
    proc = _run_cli("--rules", "lock-order", str(target))
    assert proc.returncode == 1, proc.stdout
    line = [ln for ln in proc.stdout.splitlines()
            if "lock-order" in ln][0]
    assert "_pool_lock" in line and "_sub_lock" in line
    # both chains carry file:line hops into the copy
    import re
    assert len(re.findall(r"loader_copy\.py:\d+", line)) >= 4
    assert "chain:" in line


def test_acceptance_unlocked_two_root_mutation_in_loader_copy(tmp_path):
    """Reverse direction 2: the same attribute mutated (unlocked)
    from the producer thread AND the decode-executor callee exits 1
    naming unlocked-shared-state and both roots."""
    src = open(os.path.join(REPO, "eksml_tpu", "data",
                            "loader.py")).read()
    needle1 = "            produced = 0"
    assert needle1 in src, "loader.py changed; update this probe"
    needle2 = "        rec, image = self._materialize(rec, image)"
    assert needle2 in src, "loader.py changed; update this probe"
    target = tmp_path / "loader_copy.py"
    target.write_text(
        src.replace(needle1,
                    needle1 + "\n            self._probe_stat = 0")
        .replace(needle2, needle2 + "\n        self._probe_stat = 1"))
    proc = _run_cli("--rules", "unlocked-shared-state", str(target))
    assert proc.returncode == 1, proc.stdout
    line = [ln for ln in proc.stdout.splitlines()
            if "unlocked-shared-state" in ln][0]
    assert "_probe_stat" in line
    assert "producer" in line and "executor" in line
    # the unmodified loader is clean standalone
    clean = tmp_path / "loader_clean.py"
    clean.write_text(src)
    assert _run_cli("--rules", ",".join(CONCURRENCY_RULES),
                    str(clean)).returncode == 0


# ---------------------------------------------------------------------
# thread naming (ISSUE 12 satellite): stable identities in stack dumps
# ---------------------------------------------------------------------

def test_producer_thread_is_named(fresh_config):
    """`/debugz/stacks` and the concurrency findings attribute work
    to `loader-producer`, not `Thread-3`."""
    from eksml_tpu.data import DetectionLoader, SyntheticDataset

    ds = SyntheticDataset(num_images=4, height=64, width=64)
    fresh_config.PREPROC.MAX_SIZE = 64
    fresh_config.PREPROC.TRAIN_SHORT_EDGE_SIZE = (64, 64)
    fresh_config.PREPROC.BUCKETS = ()
    loader = DetectionLoader(ds.records(), fresh_config, batch_size=2,
                             prefetch=1)
    seen = set()
    for _ in loader.batches(2):
        seen.update(t.name for t in threading.enumerate())
    assert "loader-producer" in seen, sorted(seen)


def test_named_spawn_sites_cover_runtime_threads():
    """Every production Thread/executor spawn carries an explicit
    identity (the satellite's contract: `format_thread_stacks` dumps
    attribute to stable names)."""
    import re
    unnamed = []
    for rel in ("eksml_tpu/data/loader.py",
                "eksml_tpu/telemetry/exporter.py",
                "eksml_tpu/resilience/watchdog.py",
                "eksml_tpu/evalcoco/runner.py",
                "eksml_tpu/ops/pallas/roi_align_kernel.py"):
        src = open(os.path.join(REPO, rel)).read()
        for m in re.finditer(
                r"threading\.Thread\((?:[^()]|\([^()]*\))*\)", src):
            if "name=" not in m.group(0):
                unnamed.append((rel, m.group(0)))
        for m in re.finditer(
                r"ThreadPoolExecutor\((?:[^()]|\([^()]*\))*\)", src):
            if "thread_name_prefix=" not in m.group(0):
                unnamed.append((rel, m.group(0)))
    assert unnamed == [], unnamed
