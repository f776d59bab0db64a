"""graftown fixtures and drift tests.

Every ownership rule must FIRE on its seeded violation and stay SILENT
on the paired known-false-positive shape (release in ``finally``,
conditional acquire matched by the same-condition release,
snapshot-then-restore rollback, refcount handoff to the prefix trie as
an ownership transfer).  The effect table and the inferred summaries
are then pinned in both directions, like ``test_concurrency.py`` pins
the thread-context map: dropping a primitive from the table and adding
a new lifecycle helper both show up as a diff, and every runtime
``check_invariants``/``consistency_errors`` sweep must be claimed by a
static resource kind (and vice versa).
"""

import ast
import json
import os
import subprocess
import sys
import time

import deepspeed_tpu
from deepspeed_tpu.analysis import (EFFECT_TABLE, OWN_RULE_IDS, OWN_RULES,
                                    RUNTIME_AUDIT, EffectMap,
                                    analyze_source, effect_inventory,
                                    effect_table_dict, iter_python_files)
from deepspeed_tpu.analysis.dataflow import ModuleIndex

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    deepspeed_tpu.__file__)))
SERVING = os.path.join(REPO, "deepspeed_tpu", "serving")
GRAFTLINT = os.path.join(REPO, "bin", "graftlint")


def _errors(src, rule=None):
    out = [f for f in analyze_source(src, rules=OWN_RULES)
           if f.severity == "error" and not f.suppressed]
    if rule is not None:
        out = [f for f in out if f.rule == rule]
    return out


# ------------------------------------------ leak-on-exception-path
def test_leak_on_exception_path_fires():
    src = (
        "class E:\n"
        "    def admit(self, pool, req):\n"
        "        slot = pool.alloc()\n"
        "        pool.reset_row(slot)\n"
        "        req.slot = slot\n")
    (f,) = _errors(src, "leak-on-exception-path")
    assert f.line == 3 and "slot" in f.message and "4" in f.message


def test_release_on_exception_edge_stays_silent():
    src = (
        "class E:\n"
        "    def admit(self, pool, req):\n"
        "        slot = pool.alloc()\n"
        "        try:\n"
        "            pool.reset_row(slot)\n"
        "        except Exception:\n"
        "            pool.release(slot)\n"
        "            raise\n"
        "        req.slot = slot\n")
    assert not _errors(src)


def test_release_in_finally_stays_silent():
    src = (
        "class E:\n"
        "    def locked(self):\n"
        "        self._lock.acquire()\n"
        "        try:\n"
        "            self.work()\n"
        "        finally:\n"
        "            self._lock.release()\n")
    assert not _errors(src)


# ------------------------------- cross-pool page transfer primitive
def test_transfer_import_leaks_when_seating_raises():
    # import_pages hands back an OWNED batch; seat_pages can raise, so
    # a bare import->seat with no unwind leaks the batch on that edge
    src = (
        "class E:\n"
        "    def adopt(self, pool, spool, slot, pages, pos):\n"
        "        dst = pool.import_pages(spool, pages)\n"
        "        pool.seat_pages(slot, dst, pos)\n")
    (f,) = _errors(src, "leak-on-exception-path")
    assert f.line == 3 and "page" in f.message and "4" in f.message


def test_transfer_unref_batch_on_seat_failure_stays_silent():
    # the real adopt() shape: seat_pages is atomic, so its failure
    # hands the WHOLE batch back via the bulk unref — owned-until-
    # seated, then ownership transfers into the slot table
    src = (
        "class E:\n"
        "    def adopt(self, pool, spool, slot, pages, pos):\n"
        "        dst = pool.import_pages(spool, pages)\n"
        "        try:\n"
        "            pool.seat_pages(slot, dst, pos)\n"
        "        except Exception:\n"
        "            pool.unref_pages(dst)\n"
        "            raise\n")
    assert not _errors(src)


def test_transfer_source_page_double_unref_fires():
    # the source side of a transfer drops its reference exactly once;
    # a second unref on the same handle is a double-release
    src = (
        "class E:\n"
        "    def hand_off(self, pool):\n"
        "        pid = pool.alloc_page()\n"
        "        pool.unref_page(pid)\n"
        "        pool.unref_page(pid)\n")
    (f,) = _errors(src, "double-release")
    assert f.line == 5


# ------------------------------------------------- double-release
def test_double_release_fires():
    src = (
        "class E:\n"
        "    def f(self, pool, slot):\n"
        "        pool.release(slot)\n"
        "        pool.release(slot)\n")
    (f,) = _errors(src, "double-release")
    assert f.line == 4


def test_conditional_acquire_same_condition_release_stays_silent():
    # the condition-memoisation FP shape: both guards share one test,
    # so only the (taken, taken) and (skipped, skipped) paths exist
    src = (
        "class E:\n"
        "    def f(self, pool, pid, need):\n"
        "        if need:\n"
        "            pool.ref_page(pid)\n"
        "        self.ticks = self.ticks + 1\n"
        "        if need:\n"
        "            pool.unref_page(pid)\n")
    assert not _errors(src)


# ---------------------------------------------- use-after-release
def test_use_after_release_fires():
    src = (
        "class E:\n"
        "    def f(self, pool, slot):\n"
        "        pool.release(slot)\n"
        "        pool.advance(slot)\n")
    (f,) = _errors(src, "use-after-release")
    assert f.line == 4


def test_realloc_and_seat_after_release_stays_silent():
    src = (
        "class E:\n"
        "    def f(self, pool, req, slot):\n"
        "        pool.release(slot)\n"
        "        slot = pool.alloc()\n"
        "        req.slot = slot\n"
        "        pool.advance(slot)\n")
    assert not _errors(src)


# --------------------------------------------- unbalanced-refcount
def test_unbalanced_refcount_fires():
    src = (
        "class E:\n"
        "    def f(self, pool, pid):\n"
        "        pool.ref_page(pid)\n"
        "        self.hits = self.hits + 1\n")
    (f,) = _errors(src, "unbalanced-refcount")
    assert f.line == 3


def test_returned_ref_counts_as_handoff():
    # returning the page id hands the ref to the caller — the static
    # form of `alloc_page` itself, whose caller owes the unref
    src = (
        "class E:\n"
        "    def f(self, pool, pid):\n"
        "        pool.ref_page(pid)\n"
        "        return pid\n")
    assert not _errors(src)


def test_trie_handoff_counts_as_ownership_transfer():
    # refcount handed to the prefix trie: `insert` is a transfer
    # primitive, so the ref is balanced by the handoff, not an unref
    src = (
        "class E:\n"
        "    def f(self, pool, trie, pid, key):\n"
        "        pool.ref_page(pid)\n"
        "        trie.insert(key, pid)\n")
    assert not _errors(src)


# ------------------------------------------------ missing-rollback
def test_missing_rollback_fires():
    src = (
        "class E:\n"
        "    def admit(self, req):\n"
        "        try:\n"
        "            req.state = 'PREFILLING'\n"
        "            self.pool.admit(req.slot)\n"
        "        except Exception:\n"
        "            self.log()\n"
        "            raise\n")
    (f,) = _errors(src, "missing-rollback")
    assert "state" in f.message


def test_snapshot_then_restore_stays_silent():
    src = (
        "class E:\n"
        "    def admit(self, req):\n"
        "        old = req.state\n"
        "        try:\n"
        "            req.state = 'PREFILLING'\n"
        "            self.pool.admit(req.slot)\n"
        "        except Exception:\n"
        "            req.state = old\n"
        "            raise\n")
    assert not _errors(src)


def test_own_rule_ids_are_pragma_addressable():
    # a reasoned pragma must suppress each own rule (the triage
    # workflow depends on it)
    src = (
        "class E:\n"
        "    def admit(self, pool, req):\n"
        "        slot = pool.alloc()  # graftlint: "
        "allow[leak-on-exception-path] -- fixture: deliberate\n"
        "        pool.reset_row(slot)\n"
        "        req.slot = slot\n")
    out = analyze_source(src, rules=OWN_RULES)
    assert [f.rule for f in out if f.suppressed] == \
        ["leak-on-exception-path"]
    assert not [f for f in out if f.counts_as_error]
    assert OWN_RULE_IDS == {r.id for r in OWN_RULES}


# -------------------------------------------------- effects drift
def test_effect_table_pins_every_primitive():
    """Direction one of the drift test: dropping a primitive from the
    table (or a whole kind) breaks this golden pin."""
    assert effect_table_dict() == {
        "future": {"acquire": ["create_future"],
                   "release": ["set_exception", "set_result"]},
        "lock": {"acquire": ["acquire"], "release": ["release"]},
        "page": {"acquire": ["alloc_page", "import_pages"],
                 "ref": ["ref_page"],
                 "transfer": ["insert", "map_prefix", "seat_pages",
                              "seat_prefix"],
                 "unref": ["unref_page", "unref_pages"]},
        "seat": {"acquire": ["grant"],
                 "release": ["expire", "requeue_back", "requeue_front"],
                 "use": ["submit"]},
        "slot": {"acquire": ["alloc"], "release": ["release"],
                 "release_all": ["reset"],
                 "use": ["admit", "admit_rows", "advance",
                         "cache_prefix", "ensure_writable",
                         "map_prefix", "reset_row", "run_prefill_chunk",
                         "seat_prefix"]},
    }


def test_new_lifecycle_helper_shows_up_in_effects():
    """Direction two: a new helper that releases through a table
    primitive is inferred (and propagates to its callers) without any
    table change."""
    src = (
        "class P:\n"
        "    def scrub(self, slot):\n"
        "        self.pool.release(slot)\n"
        "    def outer(self, req):\n"
        "        self.scrub(req.slot)\n")
    labels = EffectMap(ModuleIndex(ast.parse(src))).labels()
    assert labels["P.scrub"]["releases"] == ["arg1"]
    assert labels["P.outer"]["releases"] == ["arg1.slot"]


def test_effects_inventory_matches_cli_dump():
    inv = effect_inventory([SERVING])
    assert inv["table"] == effect_table_dict()
    by_base = {os.path.basename(k): v for k, v in inv["files"].items()}
    # the eviction helper is the canonical transitive release: every
    # caller of _evict_slot inherits `releases req.slot`
    assert by_base["engine.py"]["ServingEngine._evict_slot"][
        "releases"] == ["arg1.slot"]
    proc1 = subprocess.run(
        [sys.executable, GRAFTLINT, "--effects",
         os.path.join("deepspeed_tpu", "serving")],
        capture_output=True, text=True, timeout=120, cwd=str(REPO))
    assert proc1.returncode == 0, proc1.stdout + proc1.stderr
    doc = json.loads(proc1.stdout)
    assert doc["version"] == 1
    assert doc["table"] == inv["table"]
    cli_by_base = {os.path.basename(k): v
                   for k, v in doc["files"].items()}
    assert cli_by_base == by_base
    # reproducible: a second run emits byte-identical JSON
    proc2 = subprocess.run(
        [sys.executable, GRAFTLINT, "--effects",
         os.path.join("deepspeed_tpu", "serving")],
        capture_output=True, text=True, timeout=120, cwd=str(REPO))
    assert proc2.stdout == proc1.stdout


# ------------------------------------- runtime-audit cross-reference
def _serving_class_methods():
    methods = set()
    for fp in iter_python_files([SERVING]):
        with open(fp, encoding="utf-8") as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        methods.add(f"{node.name}.{sub.name}")
    return methods


def test_runtime_audit_cross_reference_both_directions():
    """Every effect-table kind names its runtime sweep, every named
    sweep exists in serving/, and every runtime
    ``check_invariants``/``consistency_errors`` definition is claimed
    by some kind — a new pool resource cannot skip the static tier."""
    assert set(RUNTIME_AUDIT) == set(EFFECT_TABLE)
    methods = _serving_class_methods()
    for kind, audits in RUNTIME_AUDIT.items():
        for qual in audits:
            assert qual in methods, (
                f"RUNTIME_AUDIT[{kind!r}] names {qual} but serving/ "
                "has no such method")
    claimed = {q for quals in RUNTIME_AUDIT.values() for q in quals}
    sweeps = {m for m in methods
              if m.split(".")[1] in ("check_invariants",
                                     "consistency_errors")}
    assert sweeps <= claimed, (
        f"runtime sweeps unclaimed by any static kind: "
        f"{sorted(sweeps - claimed)}")


# ------------------------------------------------ CLI tier budget
def test_own_cli_under_two_seconds_without_jax():
    """`bin/graftlint --tier own` over the gated surface: exit 0 with
    NO baseline, and the standalone loader must never pull in jax (what
    would take it from ~1 s to many: the probe below holds it to that).
    The bound on the wall is a guard against a hang, not a speed claim:
    2 s read 3.4 s on a loaded worker once."""
    surface = [os.path.join("deepspeed_tpu", "serving")]
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, GRAFTLINT, "--tier", "own"] + surface,
        capture_output=True, text=True, timeout=60, cwd=str(REPO))
    wall = time.monotonic() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert wall < 20.0, f"--tier own took {wall:.2f}s"
    probe = subprocess.run(
        [sys.executable, "-c",
         "import runpy, sys\n"
         "sys.argv = ['graftlint', '--tier', 'own'] + %r\n"
         "try:\n"
         "    runpy.run_path(%r, run_name='__main__')\n"
         "except SystemExit as e:\n"
         "    assert e.code == 0, e.code\n"
         "assert 'jax' not in sys.modules, 'graftlint imported jax'\n"
         % (surface, GRAFTLINT)],
        capture_output=True, text=True, timeout=60, cwd=str(REPO))
    assert probe.returncode == 0, probe.stdout + probe.stderr


def test_own_cli_fails_on_seeded_violation(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("class E:\n"
                   "    def admit(self, pool, req):\n"
                   "        slot = pool.alloc()\n"
                   "        pool.reset_row(slot)\n"
                   "        req.slot = slot\n")
    proc = subprocess.run(
        [sys.executable, GRAFTLINT, "--tier", "own", str(bad)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1
    assert "leak-on-exception-path" in proc.stdout
    # the default all-tiers run catches it too
    proc2 = subprocess.run(
        [sys.executable, GRAFTLINT, str(bad)],
        capture_output=True, text=True, timeout=60)
    assert proc2.returncode == 1
    assert "leak-on-exception-path" in proc2.stdout
    # bad path -> usage error, distinct from gate failure
    proc3 = subprocess.run(
        [sys.executable, GRAFTLINT, "--tier", "own",
         str(tmp_path / "missing.py")],
        capture_output=True, text=True, timeout=60)
    assert proc3.returncode == 2
