"""Jit-inventory drift test: graftlint statically enumerates every
jit-wrapper binding under serving/, and this test cross-checks that set
against the recompile watchdog's watch lists — a new ``self._foo =
jax.jit(...)`` in serving code fails here until it is either added to a
watch list (so post-warmup recompiles are attributed) or explicitly
justified below."""

import os

import deepspeed_tpu
from deepspeed_tpu.analysis import jit_inventory
from deepspeed_tpu.serving import engine as engine_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(
    deepspeed_tpu.__file__)))
SERVING = os.path.join(REPO, "deepspeed_tpu", "serving")
FRONTEND = os.path.join(REPO, "deepspeed_tpu", "serving", "frontend")
INFERENCE = os.path.join(REPO, "deepspeed_tpu", "inference")


def _watched():
    return (set(engine_mod._WATCHED_ENGINE_JITS)
            | set(engine_mod._WATCHED_POOL_JITS)
            | set(engine_mod._WATCHED_SERVING_JITS)
            | set(engine_mod._WATCHED_DRAFTER_JITS))


def test_every_serving_jit_is_watchdog_covered():
    inv = jit_inventory([SERVING])
    assert inv, "static jit inventory came back empty — analyzer broken?"
    unwatched = sorted({e["attr"] for e in inv} - _watched())
    assert not unwatched, (
        f"jitted entry points in serving/ not covered by any watchdog "
        f"watch list: {unwatched} — attach them in "
        "ServingEngine._ensure_watch or justify an allowlist here")


def test_inventory_finds_the_known_entry_points():
    """Pin the inventory itself: the analyzer must keep seeing the jits
    we know exist (an empty/blind inventory would make the coverage
    assertion above pass vacuously)."""
    inv = jit_inventory([SERVING])
    by_attr = {e["attr"]: e for e in inv}
    # contiguous pool: donated admit paths
    assert by_attr["_admit_jit"]["donate_argnums"] == [0]
    assert by_attr["_admit_rows_jit"]["donate_argnums"] == [0]
    # paged pool: donated cache arg sits at position 1 (after params),
    # verify carries static draft-shape argnums
    assert by_attr["_paged_decode_jit"]["donate_argnums"] == [1]
    assert by_attr["_paged_verify_jit"]["static_argnums"] == [8, 9]
    assert by_attr["_paged_chunk_jit"]["donate_argnums"] == [1]
    assert by_attr["_paged_chunk_decode_jit"]["donate_argnums"] == [1]
    assert by_attr["_jit_copy_page"]["donate_argnums"] == [0]
    # engine-local guard jit + the drafter's lazily-built argmax (the
    # escape the inventory originally caught)
    assert by_attr["_jit_finite"]["class"] == "ServingEngine"
    assert by_attr["_argmax"]["class"] == "SmallModelDrafter"


def test_frontend_has_zero_jits():
    """The async front end is pure host code by design — the engine's
    compiled surface must not grow when the HTTP/bridge/priority layer
    lands.  Any jit binding appearing under serving/frontend/ is
    inventory drift and fails here until it is watch-listed (and the
    design doc explaining why the front end compiles nothing is
    updated)."""
    inv = jit_inventory([FRONTEND])
    assert inv == [], (
        f"serving/frontend/ grew jitted entry points: "
        f"{sorted(e['attr'] for e in inv)}")


def test_watched_engine_jits_exist_in_inference_inventory():
    """The engine watch list names attributes of InferenceEngine; each
    must correspond to a real jit binding in inference/ (typo'd watch
    entries silently no-op at attach time — attach skips absentees)."""
    inv_attrs = {e["attr"] for e in jit_inventory([INFERENCE])}
    missing = sorted(set(engine_mod._WATCHED_ENGINE_JITS) - inv_attrs)
    assert not missing, (
        f"watch-listed engine jits with no jax.jit binding under "
        f"inference/: {missing}")
