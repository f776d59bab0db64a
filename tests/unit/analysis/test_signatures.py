"""graftcheck signature machinery: the static signature grammar must be
byte-identical to the runtime warmup-manifest grammar, the abstract
interpreter must enumerate the serving stack's reachable signature set
finitely, and a manifest divergence in EITHER direction must fail.

Includes the CLI subprocess tier: `bin/graftlint --check` (exit 0 on
the repo), `--check --manifest` (exit 1 on seeded divergence), and
`--inventory --signatures` (reproducible static manifest, no jax)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis.absdomain import (HOST, Arr, FiniteSet,
                                              IntRange, Known, Scalar,
                                              SignatureError, Tree, Tup,
                                              Unbounded, Unknown,
                                              expand_signatures)
from deepspeed_tpu.analysis.interp import (default_check_envs,
                                           diff_manifest, enumerate_union)
from deepspeed_tpu.telemetry.watchdog import manifest_signature

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    deepspeed_tpu.__file__)))
GRAFTLINT = os.path.join(REPO, "bin", "graftlint")


# ---------------------------------------------- grammar round-trip
def test_static_grammar_matches_runtime_grammar():
    """One call rendered by both halves must agree byte-for-byte."""
    runtime = manifest_signature(
        (np.zeros((8, 1), np.int32), np.ones((8,), np.int32),
         {"cache": None}, 0, 1.0, True),
        {"rows": np.zeros((2, 16), np.int32)})
    static = expand_signatures(
        [Arr((Known(8), Known(1)), "int32", HOST),
         Arr((Known(8),), "int32", HOST),
         Tree(HOST, "cache"), Scalar(0), Scalar(1.0), Scalar(True)],
        {"rows": Arr((Known(2), Known(16)), "int32", HOST)})
    assert static == [runtime]


def test_runtime_grammar_containers_and_scalars():
    assert manifest_signature(({"a": 1}, [1, 2], (3,)), {}) == "(*, *, *)"
    assert manifest_signature((1, 2.5, None, "x"), {}) == \
        "(1, 2.5, None, 'x')"
    assert manifest_signature((), {"b": 2, "a": 1}) == "(a=1, b=2)"


def test_expand_joint_dims_by_identity():
    # the SAME FiniteSet object in two shapes expands JOINTLY ...
    b = FiniteSet([1, 2], "B")
    sigs = expand_signatures([Arr((b, Known(1)), "float32", HOST),
                              Arr((b,), "int32", HOST)])
    assert sigs == ["(float32[1,1], int32[1])", "(float32[2,1], int32[2])"]
    # ... while two DISTINCT sets expand as a cartesian product
    sigs2 = expand_signatures(
        [Arr((FiniteSet([1, 2]), Known(1)), "float32", HOST),
         Arr((FiniteSet([1, 2]),), "int32", HOST)])
    assert len(sigs2) == 4


def test_expand_failure_modes():
    with pytest.raises(SignatureError) as e:
        expand_signatures([Arr((Unbounded("n"),), "int32", HOST)])
    assert e.value.kind == "unbounded-signature"
    with pytest.raises(SignatureError) as e2:
        expand_signatures([Unknown("host readback")])
    assert e2.value.kind == "signature-escape"
    with pytest.raises(SignatureError) as e3:
        expand_signatures([Arr((IntRange(1, 1000),), "f32", HOST),
                           Arr((IntRange(1, 1000),), "f32", HOST)])
    assert e3.value.kind == "unbounded-signature"  # product over the cap
    with pytest.raises(SignatureError) as e4:
        expand_signatures([Tup([Scalar(1)])])
    assert e4.value.kind == "signature-escape"


# ------------------------------------------- whole-stack enumeration
def test_default_envs_enumerate_finitely():
    res = enumerate_union(default_check_envs(), REPO)
    assert res.findings == []
    progs = res.programs
    # every watched program family shows up
    for name in ("InferenceEngine._jit_prefill_at",
                 "InferenceEngine._jit_decode",
                 "InferenceEngine._jit_prefill_chunk",
                 "InferenceEngine._jit_sample",
                 "SlotPool._admit_jit", "SlotPool._admit_rows_jit",
                 "SlotPool._paged_decode_jit", "SlotPool._jit_copy_page",
                 "SlotPool._paged_chunk_jit",
                 "SlotPool._paged_chunk_decode_jit"):
        assert progs.get(name), f"missing program {name}"
    # the stall-free row's admission set: singleton width buckets
    # 16..256 plus every (rows x width) group the 1024-token budget
    # allows — 19 exactly (the hand-derived count)
    pre = [s for s in progs["InferenceEngine._jit_prefill_at"]
           if "int32[1," in s]
    assert any("int32[1,16]" in s for s in pre)
    assert any("int32[1,1024]" in s for s in pre)  # serial arm bucket
    rows = progs["SlotPool._admit_rows_jit"]
    # dense 4-arg form: 8 shorts coalesce into one bucketed admit
    assert "(*, *, int32[8], int32[8])" in rows
    # paged 5-arg form carries the per-row page tables (pages_per_slot=8)
    assert "(*, *, int32[4,8], int32[4], int32[4])" in rows
    assert not any("int32[16]" in s for s in rows)  # capped at slots


def test_enumeration_is_deterministic():
    a = enumerate_union(default_check_envs(), REPO).programs
    b = enumerate_union(default_check_envs(), REPO).programs
    assert a == b


# ------------------------------------------------- manifest diffing
def _static_doc():
    envs = default_check_envs()
    res = enumerate_union(envs, REPO)
    return {"version": 1, "configs": envs,
            "programs": {k: sorted(v) for k, v in res.programs.items()}}


def test_manifest_diff_both_directions():
    doc = _static_doc()
    assert diff_manifest(doc["programs"], doc["programs"]) == []
    # static-only signature: the warmup sweep never traced it -> it
    # WILL compile post-warmup
    lean = {k: list(v) for k, v in doc["programs"].items()}
    dropped = lean["InferenceEngine._jit_decode"].pop()
    diffs = diff_manifest(doc["programs"], lean)
    assert len(diffs) == 1 and dropped in diffs[0]
    assert "never" in diffs[0] or "post-warmup" in diffs[0]
    # runtime-only signature: the static enumeration lost coverage
    fat = {k: list(v) for k, v in doc["programs"].items()}
    fat["InferenceEngine._jit_decode"] = \
        fat["InferenceEngine._jit_decode"] + ["(int32[99,99])"]
    diffs2 = diff_manifest(doc["programs"], fat)
    assert len(diffs2) == 1 and "(int32[99,99])" in diffs2[0]
    assert "missed" in diffs2[0]
    # an extra runtime-only PROGRAM is a divergence too
    extra = dict(doc["programs"])
    extra["Ghost._jit"] = ["(int32[1])"]
    assert diff_manifest(doc["programs"], extra)


# ------------------------------------------------------ CLI subprocess
def _run(args, **kw):
    return subprocess.run([sys.executable, GRAFTLINT] + args,
                          capture_output=True, text=True, timeout=120,
                          cwd=str(REPO), **kw)


def test_cli_check_manifest_match_and_divergence(tmp_path):
    doc = _static_doc()
    good = tmp_path / "signatures.json"
    good.write_text(json.dumps(doc))
    proc = _run(["--check", "--manifest", str(good)])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "matches" in proc.stdout

    doc["programs"]["InferenceEngine._jit_decode"] = \
        doc["programs"]["InferenceEngine._jit_decode"][:-1]
    bad = tmp_path / "diverged.json"
    bad.write_text(json.dumps(doc))
    proc2 = _run(["--check", "--manifest", str(bad)])
    assert proc2.returncode == 1
    assert "divergence" in proc2.stdout

    notman = tmp_path / "not_a_manifest.json"
    notman.write_text("{\"hello\": 1}")
    assert _run(["--check", "--manifest", str(notman)]).returncode == 2


def test_cli_inventory_signatures_reproducible(tmp_path):
    out = tmp_path / "static.json"
    proc = _run(["--inventory", "--signatures", str(out)])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(out.read_text())
    assert doc["version"] == 1
    assert doc["programs"] == _static_doc()["programs"]
    # bare --signatures prints the same document to stdout
    proc2 = _run(["--inventory", "--signatures"])
    assert proc2.returncode == 0
    assert json.loads(proc2.stdout)["programs"] == doc["programs"]


def test_sharded_env_enumerates_identically_to_dense():
    """The serving-tp sharded config (mesh_data=4, mesh_model=2) must
    enumerate the EXACT signature set of its dense twin: a (data, model)
    mesh moves array placements, never traced shapes — the recompile-
    free tentpole invariant, pinned at the static-analysis layer. A
    divergence here means a mesh knob leaked into a traced shape."""
    envs = default_check_envs()
    sharded = [e for e in envs if e.get("mesh_model", 1) > 1]
    assert sharded, "default_check_envs lost the serving-tp sharded env"
    (sharded_env,) = sharded
    dense_env = {k: v for k, v in sharded_env.items()
                 if k not in ("mesh_data", "mesh_model")}
    assert dense_env in envs  # the dense twin ships in the same set
    a = enumerate_union([dense_env], REPO)
    b = enumerate_union([sharded_env], REPO)
    assert a.findings == [] and b.findings == []
    assert a.programs == b.programs
