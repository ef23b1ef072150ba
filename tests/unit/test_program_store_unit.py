"""Unit tests: shared kvstore plumbing, the persistent program store's
disk behavior (round-trip, fingerprint rejection, corrupt-entry tolerance,
byte-budget LRU), program-key canonicalization, and compile-worker backoff.
"""
import os
import pickle
import time

import pytest

from dask_sql_tpu.physical import caps, identity, programs
from dask_sql_tpu.runtime import kvstore as kv
from dask_sql_tpu.runtime import program_store as ps
from dask_sql_tpu.runtime import telemetry as tel


# ---------------------------------------------------------------------------
# kvstore
# ---------------------------------------------------------------------------

def test_kvstore_read_tolerates_missing_and_corrupt(tmp_path):
    path = str(tmp_path / "s.json")
    assert kv.read_json_dict(path) == {}
    with open(path, "w") as f:
        f.write("{not json!")
    assert kv.read_json_dict(path) == {}
    with open(path, "w") as f:
        f.write('{"a": {"x": 1}, "b": 7, "c": [1]}')
    # non-dict values read as absent, dict values survive
    assert kv.read_json_dict(path) == {"a": {"x": 1}}


def test_kvstore_atomic_write_and_digest(tmp_path):
    path = str(tmp_path / "s.json")
    assert kv.atomic_write_json(path, {"k": {"v": 2}})
    assert kv.read_json_dict(path) == {"k": {"v": 2}}
    assert not kv.atomic_write_json(str(tmp_path / "no" / "dir.json"), {})
    assert kv.digest_key(("a", 1)) == kv.digest_key(("a", 1))
    assert kv.digest_key(("a", 1)) != kv.digest_key(("a", 2))


def test_kvstore_mtime_cached_file(tmp_path):
    path = str(tmp_path / "s.json")
    f = kv.MtimeCachedJsonFile(lambda: path)
    assert f.read() == {}
    f.write({"k": {"v": 1}})
    assert f.read() == {"k": {"v": 1}}
    # an external writer's update is observed (mtime invalidation)
    time.sleep(0.01)
    kv.atomic_write_json(path, {"k": {"v": 2}})
    assert f.read() == {"k": {"v": 2}}
    # corrupt file reads as empty, never raises
    with open(path, "w") as fh:
        fh.write("garbage")
    assert f.read() == {}


def test_caps_file_rides_kvstore(tmp_path, monkeypatch):
    path = str(tmp_path / "caps.json")
    monkeypatch.setenv("DSQL_CAPS_FILE", path)
    monkeypatch.setattr(caps, "_caps_disk", None)
    base_key = ("plan", (("x",),), True)
    caps._learned_caps_put(base_key, {"agg0": 8192})
    caps._learned_caps.clear()
    monkeypatch.setattr(caps, "_caps_disk", None)
    assert caps._learned_caps_get(base_key) == {"agg0": 8192}


# ---------------------------------------------------------------------------
# program store
# ---------------------------------------------------------------------------

@pytest.fixture()
def store(tmp_path, monkeypatch):
    monkeypatch.setenv("DSQL_PROGRAM_STORE", str(tmp_path / "programs"))
    monkeypatch.delenv("DSQL_PROGRAM_STORE_MB", raising=False)
    return ps.ProgramStore()


def _entry(payload: bytes = b"x" * 64) -> dict:
    return {"v": 1, "caps": {"agg0": 4096}, "spec": [], "meta": {"n_out": 1},
            "payload": payload, "n_args": 2, "n_outs": 3}


def test_store_disabled_without_env(monkeypatch):
    monkeypatch.delenv("DSQL_PROGRAM_STORE", raising=False)
    s = ps.ProgramStore()
    assert not s.enabled()
    assert not s.store("d" * 32, _entry())
    assert s.load("d" * 32) is None


def test_store_round_trip(store):
    d = store.digest(("plan", "inputs", True))
    assert not store.contains(d)
    assert store.store(d, _entry())
    assert store.contains(d)
    got = store.load(d)
    assert got is not None
    assert got["payload"] == b"x" * 64
    assert got["caps"] == {"agg0": 4096}
    assert got["fingerprint"] == ps.runtime_fingerprint()


def test_program_reloads_on_the_devices_it_was_compiled_for():
    """jax 0.9's ``deserialize_and_load`` loads onto EVERY local device by
    default; a one-device program then refuses its one-shard arguments in
    this 8-device process.  The pair in program_store carries the device
    ids across."""
    import jax
    import jax.numpy as jnp
    import pickle

    dev = jax.devices()[3]
    x = jax.device_put(jnp.arange(8.0), dev)
    compiled = jax.jit(lambda a: (a * 2, a.sum())).lower(x).compile()
    rec = pickle.loads(pickle.dumps(ps.serialize_program(compiled)))
    assert rec["devices"] == [dev.id]
    doubled, total = ps.load_program(rec, n_args=1, n_outs=2)(x)
    assert doubled.devices() == {dev} and float(total) == 28.0
    rec["devices"] = [10 ** 6]  # a device this process does not have
    with pytest.raises(KeyError):
        ps.load_program(rec, n_args=1, n_outs=2)


def test_store_miss_counts(store):
    before = tel.REGISTRY.get("program_store_misses")
    assert store.load(store.digest("never-stored")) is None
    assert tel.REGISTRY.get("program_store_misses") == before + 1


def test_fingerprint_mismatch_rejected(store):
    d = store.digest("some-program")
    store.store(d, _entry())
    # simulate an entry from a different device class / jax version landing
    # at the same digest (hand-copied store, digest collision)
    path = store._entry_path(d)
    with open(path, "rb") as f:
        raw = pickle.load(f)
    raw["fingerprint"] = dict(raw["fingerprint"], device="tpu:v9999:8")
    with open(path, "wb") as f:
        pickle.dump(raw, f)
    before = tel.REGISTRY.get("program_store_rejects")
    assert store.load(d) is None
    assert tel.REGISTRY.get("program_store_rejects") == before + 1


def test_digest_changes_with_runtime_fingerprint(store, monkeypatch):
    key = ("plan", "inputs", True)
    d1 = store.digest(key)
    monkeypatch.setattr(ps, "runtime_fingerprint",
                        lambda: {"device": "other", "jax": "0", "jaxlib": "0",
                                 "format": "1"})
    assert store.digest(key) != d1


def test_corrupt_entry_tolerated_and_dropped(store):
    d = store.digest("will-corrupt")
    store.store(d, _entry())
    with open(store._entry_path(d), "wb") as f:
        f.write(b"\x80truncated-garbage")
    before = tel.REGISTRY.get("program_store_errors")
    assert store.load(d) is None
    assert tel.REGISTRY.get("program_store_errors") == before + 1
    # the broken entry was evicted from disk and index
    assert not os.path.exists(store._entry_path(d))
    assert not store.contains(d)


def test_lru_eviction_at_byte_budget(store, monkeypatch):
    # ~2 KB payloads against a 10 KB budget: the 5th entry must evict the
    # least-recently-USED one, not simply the oldest-stored
    monkeypatch.setenv("DSQL_PROGRAM_STORE_MB", str(10 / 1024.0))
    digests = [store.digest(f"prog{i}") for i in range(5)]
    before = tel.REGISTRY.get("program_store_evictions")
    for i, d in enumerate(digests[:4]):
        assert store.store(d, _entry(payload=b"p" * 2048))
        time.sleep(0.01)
    assert store.total_bytes() <= store.budget_bytes()  # 4 entries fit
    # touch prog0 so prog1 becomes the LRU victim
    assert store.load(digests[0]) is not None
    time.sleep(0.01)
    assert store.store(digests[4], _entry(payload=b"p" * 2048))
    assert tel.REGISTRY.get("program_store_evictions") > before
    assert store.contains(digests[0])
    assert not store.contains(digests[1])
    assert store.contains(digests[4])
    assert store.total_bytes() <= store.budget_bytes()


def test_corrupt_index_tolerated(store):
    d = store.digest("indexed")
    store.store(d, _entry())
    with open(store._index_path(), "w") as f:
        f.write("not json at all")
    # index corruption degrades to "empty index": contains() misses but
    # nothing raises, and a re-store heals it
    assert store.entries() == {}
    assert store.store(d, _entry())
    assert store.contains(d)


# ---------------------------------------------------------------------------
# canonical program key (cross-process stage identity)
# ---------------------------------------------------------------------------

def test_canonical_key_rewrites_boundary_names():
    fp1 = ("Join(T|C=[@0])[s]<Scan(__split__.t0123456789abcdef)[x]<>,"
           "Scan(__split__.tfedcba9876543210)[y]<>>")
    fp2 = ("Join(T|C=[@0])[s]<Scan(__split__.taaaabbbbccccdddd)[x]<>,"
           "Scan(__split__.t1111222233334444)[y]<>>")
    k1 = identity._canonical_program_key((fp1, "inputs", True))
    k2 = identity._canonical_program_key((fp2, "inputs", True))
    # different per-process uids, same structure -> same canonical key
    assert k1 == k2
    assert "__split__.#0" in k1[0] and "__split__.#1" in k1[0]
    # REPEATED boundary names must keep their equality structure
    fp3 = ("U<Scan(__split__.t0123456789abcdef)[x]<>,"
           "Scan(__split__.t0123456789abcdef)[x]<>>")
    k3 = identity._canonical_program_key((fp3, "i", True))
    assert k3[0].count("__split__.#0") == 2
    # base-table scans are untouched
    k4 = identity._canonical_program_key(("Scan(root.t)[x]", "i", True))
    assert k4[0] == "Scan(root.t)[x]"


# ---------------------------------------------------------------------------
# compile-worker backoff
# ---------------------------------------------------------------------------

@pytest.fixture()
def _clean_streak(monkeypatch):
    monkeypatch.setattr(programs, "_compile_fail_streak", 0)
    monkeypatch.setenv("DSQL_COMPILE_WORKERS", "4")
    monkeypatch.setenv("DSQL_COMPILE_BACKOFF_AFTER", "2")
    yield
    programs._compile_fail_streak = 0


def test_compile_backoff_halves_and_recovers(_clean_streak):
    assert programs._compile_workers() == 4
    before = tel.REGISTRY.get("compile_backoffs")
    programs._note_compile_result(False)
    assert programs._compile_workers() == 4  # one failure: not yet
    programs._note_compile_result(False)
    assert programs._compile_workers() == 2  # 2 consecutive -> halved
    assert tel.REGISTRY.get("compile_backoffs") == before + 1
    programs._note_compile_result(False)
    programs._note_compile_result(False)
    assert programs._compile_workers() == 1  # 4 consecutive -> quartered
    assert tel.REGISTRY.get("compile_backoffs") == before + 2
    for _ in range(20):
        programs._note_compile_result(False)
    assert programs._compile_workers() == 1  # floor of one worker
    programs._note_compile_result(True)
    assert programs._compile_workers() == 4  # any success restores


def test_compile_backoff_respects_stage_cap(_clean_streak):
    assert programs._compile_workers(2) == 2
    programs._note_compile_result(False)
    programs._note_compile_result(False)
    assert programs._compile_workers(8) == 2
    assert programs._compile_workers(1) == 1
