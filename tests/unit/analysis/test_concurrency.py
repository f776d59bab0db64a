"""graftsync fixtures and drift tests.

Every sync rule must FIRE on its seeded violation and stay SILENT on
the paired known-false-positive shape (executor-wrapped blocking call,
``call_soon_threadsafe``-wrapped resolution, lock released before the
``await``, both-sides-locked shared write).  The thread-context map is
then pinned against the real front end in both directions, like
``test_inventory.py`` pins the jit inventory: every coroutine must
infer LOOP, every ``step()`` caller must infer ENGINE, and the named
bridge crossings must keep their exact labels.
"""

import ast
import json
import os
import subprocess
import sys
import time

import deepspeed_tpu
from deepspeed_tpu.analysis import (SYNC_RULE_IDS, SYNC_RULES,
                                    ThreadContextMap, analyze_source,
                                    iter_python_files, thread_inventory)
from deepspeed_tpu.analysis.dataflow import ModuleIndex, node_path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    deepspeed_tpu.__file__)))
FRONTEND = os.path.join(REPO, "deepspeed_tpu", "serving", "frontend")
GRAFTLINT = os.path.join(REPO, "bin", "graftlint")


def _errors(src, rule=None):
    out = [f for f in analyze_source(src, rules=SYNC_RULES)
           if f.severity == "error" and not f.suppressed]
    if rule is not None:
        out = [f for f in out if f.rule == rule]
    return out


# ------------------------------------- blocking-call-in-coroutine
def test_blocking_sleep_in_coroutine_fires():
    src = (
        "import time\n"
        "async def handler():\n"
        "    time.sleep(0.1)\n")
    (f,) = _errors(src, "blocking-call-in-coroutine")
    assert f.line == 3 and "time.sleep" in f.message


def test_blocking_variants_fire():
    src = (
        "import queue\n"
        "import threading\n"
        "class B:\n"
        "    def __init__(self):\n"
        "        self._ops = queue.Queue()\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "    def _run(self):\n"
        "        pass\n"
        "    async def h(self, srv, sock, x):\n"
        "        fh = open('/tmp/x')\n"
        "        sock.recv(4096)\n"
        "        srv.step()\n"
        "        x.block_until_ready()\n"
        "        self._t.join()\n"
        "        self._ops.get()\n")
    found = _errors(src, "blocking-call-in-coroutine")
    assert len(found) == 6, [f.message for f in found]
    blob = " ".join(f.message for f in found)
    for needle in ("file I/O", ".recv", "step()", "block_until_ready",
                   ".join()", ".get()"):
        assert needle in blob, (needle, blob)


def test_blocking_known_fp_shapes_stay_silent():
    # executor handoff, awaited async equivalents, and non-blocking
    # queue access are the sanctioned idioms — none may fire
    src = (
        "import asyncio\n"
        "import queue\n"
        "import time\n"
        "class B:\n"
        "    def __init__(self):\n"
        "        self._ops = queue.Queue()\n"
        "    async def h(self, loop, t):\n"
        "        await asyncio.sleep(0.1)\n"
        "        def work():\n"
        "            time.sleep(1.0)\n"
        "        await loop.run_in_executor(None, work)\n"
        "        await loop.run_in_executor(None, t.join)\n"
        "        self._ops.get_nowait()\n"
        "        self._ops.get(block=False)\n")
    assert _errors(src, "blocking-call-in-coroutine") == []


# ------------------------------------- cross-thread-engine-access
def test_cross_thread_engine_read_fires():
    src = (
        "class Frontend:\n"
        "    async def stats(self):\n"
        "        return self.srv.scheduler.pending\n")
    (f,) = _errors(src, "cross-thread-engine-access")
    assert "self.srv.scheduler" in f.message and "bridge.call" in f.message


def test_cross_thread_engine_write_fires():
    src = (
        "class Frontend:\n"
        "    async def pause(self, srv):\n"
        "        srv.paused = True\n")
    (f,) = _errors(src, "cross-thread-engine-access")
    assert "srv.paused" in f.message


def test_bridge_call_handoff_stays_silent():
    # the sanctioned read path: the lambda/function handed to
    # bridge.call runs on the step thread, so its engine access is legal
    src = (
        "class Frontend:\n"
        "    async def stats(self):\n"
        "        n = await self.bridge.call(\n"
        "            lambda srv: srv.scheduler.pending)\n"
        "        def probe(srv):\n"
        "            return srv.live_count\n"
        "        m = await self.bridge.call(probe)\n"
        "        return n + m\n")
    assert _errors(src, "cross-thread-engine-access") == []


# --------------------------------------- unsafe-future-resolution
def test_off_loop_set_result_fires():
    src = (
        "import threading\n"
        "def worker(fut):\n"
        "    fut.set_result(1)\n"
        "t = threading.Thread(target=worker)\n")
    (f,) = _errors(src, "unsafe-future-resolution")
    assert "call_soon_threadsafe" in f.message


def test_call_soon_threadsafe_wrapped_resolution_stays_silent():
    # the bridge's _resolve shape: the setter runs as a loop callback,
    # so its set_result is on-loop even though the scheduler is not
    src = (
        "import threading\n"
        "class B:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self.worker)\n"
        "    def worker(self):\n"
        "        self.loop.call_soon_threadsafe(self._set, self.fut, 1)\n"
        "    def _set(self, fut, v):\n"
        "        if not fut.done():\n"
        "            fut.set_result(v)\n")
    assert _errors(src, "unsafe-future-resolution") == []


def test_concurrent_futures_receiver_stays_silent():
    src = (
        "import threading\n"
        "def worker(fut: 'concurrent.futures.Future'):\n"
        "    fut.set_result(1)\n"
        "t = threading.Thread(target=worker)\n")
    assert _errors(src, "unsafe-future-resolution") == []


# --------------------------------------- await-while-holding-lock
def test_await_inside_lock_fires():
    src = (
        "import threading\n"
        "_lock = threading.Lock()\n"
        "async def h(q):\n"
        "    with _lock:\n"
        "        item = await q.get()\n"
        "    return item\n")
    (f,) = _errors(src, "await-while-holding-lock")
    assert f.line == 5 and "_lock" in f.message


def test_lock_released_before_await_stays_silent():
    src = (
        "import threading\n"
        "_lock = threading.Lock()\n"
        "async def h(q):\n"
        "    with _lock:\n"
        "        item = prepare()\n"
        "    return await q.put(item)\n")
    assert _errors(src, "await-while-holding-lock") == []


def test_inconsistent_lock_order_fires_once():
    src = (
        "import threading\n"
        "a = threading.Lock()\n"
        "b = threading.Lock()\n"
        "def f():\n"
        "    with a:\n"
        "        with b:\n"
        "            pass\n"
        "def g():\n"
        "    with b:\n"
        "        with a:\n"
        "            pass\n")
    (f,) = _errors(src, "await-while-holding-lock")
    assert "AB/BA" in f.message or "opposite order" in f.message


def test_consistent_lock_order_stays_silent():
    src = (
        "import threading\n"
        "a = threading.Lock()\n"
        "b = threading.Lock()\n"
        "def f():\n"
        "    with a:\n"
        "        with b:\n"
        "            pass\n"
        "def g():\n"
        "    with a:\n"
        "        with b:\n"
        "            pass\n")
    assert _errors(src, "await-while-holding-lock") == []


# ----------------------------------------- unguarded-shared-write
_SHARED_WRITE_SRC = (
    "import threading\n"
    "class B:\n"
    "    def __init__(self):\n"
    "        self._lk = threading.Lock()\n"
    "    def start(self):\n"
    "        self._t = threading.Thread(target=self._run)\n"
    "    async def stop(self):\n"
    "        {loop_write}\n"
    "    def _run(self):\n"
    "        {engine_write}\n")


def test_unguarded_shared_write_fires():
    src = _SHARED_WRITE_SRC.format(loop_write="self.items.clear()",
                                   engine_write="self.items[1] = 2")
    (f,) = _errors(src, "unguarded-shared-write")
    assert "self.items" in f.message and "LOOP" in f.message \
        and "ENGINE" in f.message


def test_both_sides_locked_stays_silent():
    src = _SHARED_WRITE_SRC.format(
        loop_write="\n        ".join(
            ["with self._lk:", "    self.items.clear()"]),
        engine_write="\n        ".join(
            ["with self._lk:", "    self.items[1] = 2"]))
    assert _errors(src, "unguarded-shared-write") == []


def test_single_sided_write_stays_silent():
    src = _SHARED_WRITE_SRC.format(loop_write="pass",
                                   engine_write="self.items[1] = 2")
    assert _errors(src, "unguarded-shared-write") == []


# ---------------------------------------- thread-context map drift
def _frontend_maps():
    out = {}
    for fp in iter_python_files([FRONTEND]):
        with open(fp, encoding="utf-8") as fh:
            source = fh.read()
        tree = ast.parse(source, filename=fp)
        index = ModuleIndex(tree)
        out[os.path.basename(fp)] = (index,
                                     ThreadContextMap(index).labels())
    return out


def test_every_frontend_coroutine_is_loop():
    """Direction 1: each `async def` in serving/frontend infers exactly
    LOOP — a coroutine drifting to ENGINE/BOTH means the inference (or
    the front end's threading discipline) broke."""
    checked = 0
    for fname, (index, labels) in _frontend_maps().items():
        for fi in index.functions.values():
            if not isinstance(fi.node, ast.AsyncFunctionDef):
                continue
            checked += 1
            assert labels.get(fi.qualname) == "LOOP", (
                f"{fname}:{fi.qualname} inferred "
                f"{labels.get(fi.qualname)}, expected LOOP")
    assert checked >= 10, f"only {checked} coroutines found — drift?"


def test_every_step_caller_is_engine_only():
    """Direction 2: any frontend function that calls `.step()` on an
    engine root must infer exactly ENGINE — step() leaking into LOOP
    or BOTH context is the incident this tier exists to prevent."""
    checked = 0
    for fname, (index, labels) in _frontend_maps().items():
        for fi in index.functions.values():
            calls_step = any(
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "step"
                and (node_path(n.func.value) or "").split(".")[-1]
                    .lstrip("_") in ("srv", "engine")
                for n in ast.walk(fi.node))
            if not calls_step:
                continue
            checked += 1
            assert labels.get(fi.qualname) == "ENGINE", (
                f"{fname}:{fi.qualname} calls step() but inferred "
                f"{labels.get(fi.qualname)}")
    assert checked >= 1, "no step() caller found in frontend — drift?"


def test_bridge_crossing_labels_pinned():
    """The named crossings keep their exact labels: the
    call_soon_threadsafe callbacks are LOOP, the op-queue consumers are
    ENGINE, and _emit (called from stop() and the step thread) is the
    one BOTH function."""
    _, labels = _frontend_maps()["bridge.py"]
    expected = {
        "AsyncEngineBridge.start": "LOOP",
        "AsyncEngineBridge.stop": "LOOP",
        "AsyncEngineBridge.submit": "LOOP",
        "AsyncEngineBridge.call": "LOOP",
        "AsyncEngineBridge._set_result": "LOOP",
        "AsyncEngineBridge._set_exception": "LOOP",
        "AsyncEngineBridge._deliver": "LOOP",
        "AsyncEngineBridge._run": "ENGINE",
        "AsyncEngineBridge._loop_body": "ENGINE",
        "AsyncEngineBridge._apply_op": "ENGINE",
        "AsyncEngineBridge._fan_out": "ENGINE",
        "AsyncEngineBridge._emit": "BOTH",
        "AsyncEngineBridge._reject_pending_ops": "BOTH",
        # _reject is reachable from _apply_op (ENGINE) and from stop()'s
        # leftover-op rejection (LOOP) — safe on both sides because it
        # marshals through call_soon_threadsafe
        "AsyncEngineBridge._reject": "BOTH",
        "AsyncEngineBridge._resolve": "ENGINE",
    }
    for qual, want in expected.items():
        assert labels.get(qual) == want, (qual, labels.get(qual), want)
    # and BOTH stays the exception, not the rule: only the documented
    # crossing helpers may run on either side
    both = sorted(q for q, v in labels.items() if v == "BOTH")
    assert both == ["AsyncEngineBridge._emit",
                    "AsyncEngineBridge._reject",
                    "AsyncEngineBridge._reject_pending_ops"], both


def test_thread_inventory_matches_cli_dump():
    inv = thread_inventory([FRONTEND])
    by_base = {os.path.basename(k): v for k, v in inv.items()}
    assert by_base["bridge.py"]["AsyncEngineBridge._apply_op"] == "ENGINE"
    proc1 = subprocess.run(
        [sys.executable, GRAFTLINT, "--threads",
         os.path.join("deepspeed_tpu", "serving", "frontend")],
        capture_output=True, text=True, timeout=120, cwd=str(REPO))
    assert proc1.returncode == 0, proc1.stdout + proc1.stderr
    doc = json.loads(proc1.stdout)
    assert doc["version"] == 1
    cli_by_base = {os.path.basename(k): v
                   for k, v in doc["files"].items()}
    assert cli_by_base == by_base
    # reproducible: a second run emits byte-identical JSON
    proc2 = subprocess.run(
        [sys.executable, GRAFTLINT, "--threads",
         os.path.join("deepspeed_tpu", "serving", "frontend")],
        capture_output=True, text=True, timeout=120, cwd=str(REPO))
    assert proc2.stdout == proc1.stdout


# ------------------------------------------------ CLI tier budget
def test_sync_cli_under_two_seconds_without_jax():
    """`bin/graftlint --tier sync` over the gated surface: exit 0, and
    the standalone loader must never pull in jax (the probe below). The
    bound on the wall is a guard against a hang, not a speed claim: 2 s
    read 3.4 s on a loaded worker once."""
    surface = [os.path.join("deepspeed_tpu", "serving", "frontend"),
               os.path.join("deepspeed_tpu", "serving", "engine.py"),
               os.path.join("deepspeed_tpu", "telemetry")]
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, GRAFTLINT, "--tier", "sync"] + surface,
        capture_output=True, text=True, timeout=60, cwd=str(REPO))
    wall = time.monotonic() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert wall < 20.0, f"--tier sync took {wall:.2f}s"
    probe = subprocess.run(
        [sys.executable, "-c",
         "import runpy, sys\n"
         "sys.argv = ['graftlint', '--tier', 'sync'] + %r\n"
         "try:\n"
         "    runpy.run_path(%r, run_name='__main__')\n"
         "except SystemExit as e:\n"
         "    assert e.code == 0, e.code\n"
         "assert 'jax' not in sys.modules, 'graftlint imported jax'\n"
         % (surface, GRAFTLINT)],
        capture_output=True, text=True, timeout=60, cwd=str(REPO))
    assert probe.returncode == 0, probe.stdout + probe.stderr


def test_sync_cli_fails_on_seeded_violation(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\n"
                   "async def handler():\n"
                   "    time.sleep(1)\n")
    proc = subprocess.run(
        [sys.executable, GRAFTLINT, "--tier", "sync", str(bad)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1
    assert "blocking-call-in-coroutine" in proc.stdout
    # the default all-tiers run catches it too
    proc2 = subprocess.run(
        [sys.executable, GRAFTLINT, str(bad)],
        capture_output=True, text=True, timeout=60)
    assert proc2.returncode == 1
    assert "blocking-call-in-coroutine" in proc2.stdout


def test_sync_rule_ids_are_pragma_addressable():
    # a reasoned pragma must suppress each sync rule (the triage
    # workflow depends on it)
    src = (
        "import time\n"
        "async def handler():\n"
        "    time.sleep(1)  # graftlint: allow[blocking-call-in-coroutine]"
        " -- fixture: deliberate\n")
    out = analyze_source(src, rules=SYNC_RULES)
    assert [f.rule for f in out if f.suppressed] == \
        ["blocking-call-in-coroutine"]
    assert not [f for f in out if f.counts_as_error]
    assert SYNC_RULE_IDS == {r.id for r in SYNC_RULES}
