"""The persistent AOT executable cache (accelerate_tpu/compile_cache/):
crash-safe commits, defensive reads, quarantine-on-corruption, eviction
semantics, the kill switch, and the warm-restart consumers (ISSUE 13).

The invariants under test: a poisoned/torn/mismatched entry must NEVER crash
a restart or load the wrong executable (fallback compile + quarantine,
always); a kill -9 at any point of a store leaves only committed entries;
the cache key is stable across processes (or there is no warm restart); and
``ACCELERATE_COMPILE_CACHE=0`` is byte-identical to an uncached build.
"""

import json
import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accelerate_tpu import compile_cache as cc
from accelerate_tpu.compile_cache.cache import CompileCache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(p for p in (REPO, env.get("PYTHONPATH")) if p)
    return env


@pytest.fixture(scope="module")
def step_fn():
    def step(p, x):
        return {"w": p["w"] - 0.1 * (p["w"] @ x)[:, None] * x[None, :]}

    return jax.jit(step)


@pytest.fixture(scope="module")
def step_args():
    return ({"w": jnp.ones((8, 8))}, jnp.ones((8,)))


def _populate(cache_dir, step_fn, step_args, name="step"):
    executable, outcome = cc.aot_compile(name, step_fn, step_args, directory=str(cache_dir))
    assert executable is not None
    return outcome


# ---------------------------------------------------------------------------
# store/load roundtrip + commit protocol


def test_miss_store_hit_roundtrip(tmp_path, step_fn, step_args):
    assert _populate(tmp_path, step_fn, step_args) == "miss"
    executable, outcome = cc.aot_compile("step", step_fn, step_args, directory=str(tmp_path))
    assert outcome == "hit"
    ref = step_fn(*step_args)
    got = executable(*step_args)
    np.testing.assert_array_equal(np.asarray(ref["w"]), np.asarray(got["w"]))
    cache = CompileCache(str(tmp_path))
    assert cache.stats()["entries"] == 1
    entry = cache.entries()[0]
    manifest = json.load(open(os.path.join(entry, cc.MANIFEST_NAME)))
    assert manifest["schema"] == cc.SCHEMA_VERSION
    assert manifest["payload"]["bytes"] == os.path.getsize(
        os.path.join(entry, cc.PAYLOAD_NAME)
    )


def test_load_only_probe_never_compiles(tmp_path, step_fn, step_args):
    from accelerate_tpu.telemetry import step_profiler as sp

    sp.install_compile_listener()
    loaded, key = cc.maybe_load_executable("step", step_fn, step_args, directory=str(tmp_path))
    assert loaded is None  # empty cache: miss, and load-only must NOT compile
    _populate(tmp_path, step_fn, step_args)
    c0 = sp.compile_snapshot()[0]
    loaded, key = cc.maybe_load_executable("step", step_fn, step_args, directory=str(tmp_path))
    assert loaded is not None and key is not None
    got = loaded(*step_args)
    assert sp.compile_snapshot()[0] == c0  # zero backend compiles on the warm path
    np.testing.assert_array_equal(
        np.asarray(step_fn(*step_args)["w"]), np.asarray(got["w"])
    )


def test_key_changes_with_fingerprint_and_identity_fields(step_fn, step_args):
    lowered = step_fn.lower(*step_args)
    k1 = cc.key_from_lowered("step", lowered)
    k2 = cc.key_from_lowered("renamed", lowered)
    assert k1.entry_id == k2.entry_id  # fn name is informational, not identity
    other = jax.jit(lambda p, x: {"w": p["w"] + x.sum()}).lower(*step_args)
    assert cc.key_from_lowered("step", other).entry_id != k1.entry_id
    import dataclasses

    bumped = dataclasses.replace(k1, jaxlib_version="9.9.9")
    assert bumped.entry_id != k1.entry_id
    retopo = dataclasses.replace(k1, mesh_axes=(("dp", 4),))
    assert retopo.entry_id != k1.entry_id


# ---------------------------------------------------------------------------
# defensive reads: corrupt / truncated / version / topology / swapped


def _entry(cache_dir):
    cache = CompileCache(str(cache_dir))
    entries = cache.entries()
    assert entries, "no committed entry"
    return cache, entries[0]


def _assert_fallback(tmp_path, step_fn, step_args, expect_reason_substr):
    """The poisoned load must report corrupt (never an executable), the entry
    must be quarantined, and the fallback compile must still be correct."""
    executable, outcome = cc.aot_compile("step", step_fn, step_args, directory=str(tmp_path))
    assert outcome == "corrupt"
    assert executable is not None  # the FALLBACK compile, not a cache load
    np.testing.assert_array_equal(
        np.asarray(step_fn(*step_args)["w"]), np.asarray(executable(*step_args)["w"])
    )
    cache = CompileCache(str(tmp_path))
    assert cache.stats()["quarantined"] >= 1
    qdir = cache.quarantine_dir()
    reasons = ""
    for q in os.listdir(qdir):
        reason_file = os.path.join(qdir, q, "QUARANTINE_REASON")
        if os.path.isfile(reason_file):
            reasons += open(reason_file).read()
    assert expect_reason_substr in reasons


def test_bitflipped_payload_quarantined_and_fallback(tmp_path, step_fn, step_args):
    _populate(tmp_path, step_fn, step_args)
    _, entry = _entry(tmp_path)
    payload = os.path.join(entry, cc.PAYLOAD_NAME)
    blob = bytearray(open(payload, "rb").read())
    blob[len(blob) // 3] ^= 0xFF
    open(payload, "wb").write(bytes(blob))
    _assert_fallback(tmp_path, step_fn, step_args, "CRC32 mismatch")


def test_truncated_payload_quarantined(tmp_path, step_fn, step_args):
    _populate(tmp_path, step_fn, step_args)
    _, entry = _entry(tmp_path)
    payload = os.path.join(entry, cc.PAYLOAD_NAME)
    blob = open(payload, "rb").read()
    open(payload, "wb").write(blob[: len(blob) // 2])
    _assert_fallback(tmp_path, step_fn, step_args, "truncated")


def test_version_mismatch_never_loads(tmp_path, step_fn, step_args):
    """A manifest claiming a different jaxlib under OUR entry id can only be
    tampering/corruption (an honest version difference hashes elsewhere) —
    quarantine + fallback, never a load."""
    _populate(tmp_path, step_fn, step_args)
    _, entry = _entry(tmp_path)
    mpath = os.path.join(entry, cc.MANIFEST_NAME)
    manifest = json.load(open(mpath))
    manifest["key"]["jaxlib_version"] = "0.0.1"
    json.dump(manifest, open(mpath, "w"))
    _assert_fallback(tmp_path, step_fn, step_args, "jaxlib_version")


def test_topology_mismatch_never_loads(tmp_path, step_fn, step_args):
    _populate(tmp_path, step_fn, step_args)
    _, entry = _entry(tmp_path)
    mpath = os.path.join(entry, cc.MANIFEST_NAME)
    manifest = json.load(open(mpath))
    manifest["key"]["num_devices"] = 4096
    manifest["key"]["mesh_axes"] = [["dp", 4096]]
    json.dump(manifest, open(mpath, "w"))
    _assert_fallback(tmp_path, step_fn, step_args, "mismatch")


def test_unparseable_manifest_quarantined(tmp_path, step_fn, step_args):
    _populate(tmp_path, step_fn, step_args)
    _, entry = _entry(tmp_path)
    open(os.path.join(entry, cc.MANIFEST_NAME), "w").write("{torn json")
    _assert_fallback(tmp_path, step_fn, step_args, "unparseable")


def test_swapped_manifests_both_refused(tmp_path, step_args):
    """The chaos 'swap manifests' case: two committed entries whose manifests
    are exchanged must BOTH fail key verification — neither may load the
    other's executable."""
    f1 = jax.jit(lambda p, x: {"w": p["w"] * 2.0})
    f2 = jax.jit(lambda p, x: {"w": p["w"] + x.sum()})
    _populate(tmp_path, f1, step_args, name="f1")
    _populate(tmp_path, f2, step_args, name="f2")
    cache = CompileCache(str(tmp_path))
    e1, e2 = cache.entries()
    m1, m2 = (os.path.join(e, cc.MANIFEST_NAME) for e in (e1, e2))
    blob1, blob2 = open(m1).read(), open(m2).read()
    open(m1, "w").write(blob2)
    open(m2, "w").write(blob1)
    for fn, name in ((f1, "f1"), (f2, "f2")):
        executable, outcome = cc.aot_compile(name, fn, step_args, directory=str(tmp_path))
        assert outcome == "corrupt"
        np.testing.assert_array_equal(
            np.asarray(fn(*step_args)["w"]), np.asarray(executable(*step_args)["w"])
        )


def test_corrupt_pickle_payload_with_valid_crc(tmp_path, step_fn, step_args):
    """A payload whose CRC *matches* (manifest rewritten consistently) but
    whose pickled content is garbage must still fall back — the deserialize
    failure path, not the CRC path."""
    import zlib

    _populate(tmp_path, step_fn, step_args)
    _, entry = _entry(tmp_path)
    payload_path = os.path.join(entry, cc.PAYLOAD_NAME)
    garbage = pickle.dumps(("not", "an", "executable"))
    open(payload_path, "wb").write(garbage)
    mpath = os.path.join(entry, cc.MANIFEST_NAME)
    manifest = json.load(open(mpath))
    manifest["payload"]["bytes"] = len(garbage)
    manifest["payload"]["crc32"] = zlib.crc32(garbage) & 0xFFFFFFFF
    json.dump(manifest, open(mpath, "w"))
    _assert_fallback(tmp_path, step_fn, step_args, "deserialize")


# ---------------------------------------------------------------------------
# crash consistency + writer races


@pytest.mark.slow  # subprocess pays a jax import
def test_kill9_mid_write_leaves_only_committed_entries(tmp_path, step_fn, step_args):
    """A seeded SIGKILL at the compile_cache_store chaos point (payload
    written, manifest NOT committed) must leave zero committed entries — only
    an orphaned staging dir, which the next store sweeps."""
    cache_dir = tmp_path / "cache"
    child = (
        "import os, json\n"
        "import jax, jax.numpy as jnp\n"
        "from accelerate_tpu.resilience.chaos import ChaosSchedule, Fault, arm\n"
        "from accelerate_tpu import compile_cache as cc\n"
        "arm(ChaosSchedule(faults=[Fault(kind='sigkill', point='compile_cache_store')]))\n"
        "f = jax.jit(lambda p, x: {'w': p['w'] - 0.1 * (p['w'] @ x)[:, None] * x[None, :]})\n"
        f"cc.aot_compile('step', f, ({{'w': jnp.ones((8, 8))}}, jnp.ones((8,))), directory={str(cache_dir)!r})\n"
        "print('UNREACHABLE')\n"
    )
    res = subprocess.run(
        [sys.executable, "-c", child], env=_child_env(), capture_output=True,
        text=True, timeout=240,
    )
    assert res.returncode == -9, (res.returncode, res.stderr[-500:])
    assert "UNREACHABLE" not in res.stdout
    cache = CompileCache(str(cache_dir))
    assert cache.entries() == []  # nothing committed
    staging = [n for n in os.listdir(cache_dir) if ".tmp-" in n]
    assert staging  # the torn write is visible as staging, not as an entry
    # the next writer sweeps the orphan (age floor zeroed for the test) and
    # commits a real entry
    cache._sweep_stale_staging(max_age_s=0.0)
    assert [n for n in os.listdir(cache_dir) if ".tmp-" in n] == []
    assert _populate(cache_dir, step_fn, step_args) == "miss"
    assert len(cache.entries()) == 1


def test_concurrent_writers_race_benignly(tmp_path, step_fn, step_args):
    """First rename wins; the second writer discards its staging and reports
    `raced` — the committed entry stays valid either way."""
    lowered = step_fn.lower(*step_args)
    key = cc.key_from_lowered("step", lowered)
    compiled = lowered.compile()
    cache = CompileCache(str(tmp_path))
    r1 = cache.store(key, compiled)
    r2 = cache.store(key, compiled)
    assert r1.outcome == "stored" and r2.outcome == "raced"
    assert cache.load(key).outcome == "hit"
    assert [n for n in os.listdir(tmp_path) if ".tmp-" in n] == []


def test_true_rename_race_loser_discards(tmp_path, step_fn, step_args, monkeypatch):
    """Two stagings for the same key racing through os.rename: the loser's
    rename targets an existing non-empty dir, fails, and is discarded."""
    lowered = step_fn.lower(*step_args)
    key = cc.key_from_lowered("step", lowered)
    compiled = lowered.compile()
    cache = CompileCache(str(tmp_path))
    real_rename = os.rename
    committed_first = {}

    def racing_rename(src, dst):
        # the other writer commits between our manifest write and our rename
        if not committed_first and ".tmp-" in src:
            committed_first["done"] = True
            CompileCache(str(tmp_path)).store(key, compiled)
        return real_rename(src, dst)

    monkeypatch.setattr(os, "rename", racing_rename)
    res = cache.store(key, compiled)
    monkeypatch.undo()
    assert res.outcome == "raced"
    assert cache.load(key).outcome == "hit"
    assert [n for n in os.listdir(tmp_path) if ".tmp-" in n] == []


# ---------------------------------------------------------------------------
# eviction


def _fake_entry(cache_dir, key_id, nbytes=1024, mtime=None, fn=None):
    """Hand-built committed entry (content is irrelevant to eviction);
    ``fn`` labels the manifest for the per-function quota grouping."""
    import zlib

    entry = os.path.join(str(cache_dir), key_id)
    os.makedirs(entry)
    payload = os.urandom(nbytes)
    open(os.path.join(entry, cc.PAYLOAD_NAME), "wb").write(payload)
    json.dump(
        {"schema": cc.SCHEMA_VERSION, "key": {}, "fn": fn or key_id,
         "payload": {"file": cc.PAYLOAD_NAME, "bytes": nbytes,
                     "crc32": zlib.crc32(payload) & 0xFFFFFFFF}},
        open(os.path.join(entry, cc.MANIFEST_NAME), "w"),
    )
    if mtime is not None:
        os.utime(entry, (mtime, mtime))
    return entry


def test_eviction_oldest_first_under_cap(tmp_path):
    old = _fake_entry(tmp_path, "a" * 24, nbytes=600 * 1024, mtime=1_000)
    new = _fake_entry(tmp_path, "b" * 24, nbytes=600 * 1024, mtime=2_000)
    cache = CompileCache(str(tmp_path))
    evicted = cache.evict(max_mb=1.0)
    assert evicted == [old]
    assert os.path.isdir(new) and not os.path.isdir(old)


def test_eviction_skips_entry_open_for_read(tmp_path):
    import fcntl

    victim = _fake_entry(tmp_path, "a" * 24, nbytes=600 * 1024, mtime=1_000)
    other = _fake_entry(tmp_path, "b" * 24, nbytes=600 * 1024, mtime=2_000)
    cache = CompileCache(str(tmp_path))
    reader = open(os.path.join(victim, cc.MANIFEST_NAME), "rb")
    try:
        fcntl.flock(reader.fileno(), fcntl.LOCK_SH)  # a load in flight
        evicted = cache.evict(max_mb=0.0)
        # the reader-held entry survives even under a zero cap; the idle one
        # goes
        assert victim not in evicted and os.path.isdir(victim)
        assert other in evicted and not os.path.isdir(other)
    finally:
        reader.close()
    assert cache.evict(max_mb=0.0) == [victim]  # released: now evictable


def test_eviction_hit_refreshes_recency(tmp_path, step_fn, step_args):
    """GC is LRU-by-last-HIT, not oldest-write: a load stamps the entry's
    recency, so the executable a fleet actually reloads outlives a
    never-read entry written later (ISSUE 14 compile-cache GC upgrade)."""
    assert _populate(tmp_path, step_fn, step_args) == "miss"
    cache = CompileCache(str(tmp_path))
    (hot,) = cache.entries()
    os.utime(hot, (1_000, 1_000))  # backdate: oldest-write would evict it
    stale = _fake_entry(tmp_path, "b" * 24, nbytes=600 * 1024, mtime=2_000)
    key = cc.key_from_lowered("step", step_fn.lower(*step_args))
    assert cache.load(key).outcome == "hit"  # stamps LAST_HIT on `hot`
    assert os.path.isfile(os.path.join(hot, cc.LAST_HIT_NAME))
    assert cache.entries() == [stale, hot]  # recency order flipped
    evicted = cache.evict(max_mb=0.55)
    assert stale in evicted and hot not in evicted and os.path.isdir(hot)
    # and the hot entry still loads after the GC pass
    assert cache.load(key).outcome == "hit"


def test_eviction_fn_quota_spares_other_fns(tmp_path):
    """Per-fn quota: a function over its share sheds its OWN least-recently
    -hit entries; another function's globally-older entry is untouched."""
    a1 = _fake_entry(tmp_path, "a" * 24, nbytes=500 * 1024, mtime=1_000, fn="lattice")
    a2 = _fake_entry(tmp_path, "b" * 24, nbytes=500 * 1024, mtime=2_000, fn="lattice")
    a3 = _fake_entry(tmp_path, "c" * 24, nbytes=500 * 1024, mtime=3_000, fn="lattice")
    b1 = _fake_entry(tmp_path, "d" * 24, nbytes=500 * 1024, mtime=1_500, fn="train_step")
    cache = CompileCache(str(tmp_path), fn_quota_mb=1.0)
    evicted = cache.evict()  # quota enforcement needs no global cap
    # lattice holds 1.5MB against a 1MB share: its LRU entry goes; train_step
    # is under quota, so its OLDER entry survives a pass that oldest-write
    # eviction would have taken it in
    assert evicted == [a1]
    assert os.path.isdir(b1) and os.path.isdir(a2) and os.path.isdir(a3)
    assert not os.path.isdir(a1)


def test_eviction_fn_quota_env_knob_then_global_cap(tmp_path, monkeypatch):
    """The env knob wires the quota, and the global cap still applies after
    the quota pass — across functions, least-recently-hit first."""
    a1 = _fake_entry(tmp_path, "a" * 24, nbytes=400 * 1024, mtime=1_000, fn="lattice")
    a2 = _fake_entry(tmp_path, "b" * 24, nbytes=400 * 1024, mtime=3_000, fn="lattice")
    b1 = _fake_entry(tmp_path, "c" * 24, nbytes=400 * 1024, mtime=2_000, fn="train_step")
    monkeypatch.setenv(cc.CACHE_FN_QUOTA_MB_ENV_VAR, "0.5")
    cache = CompileCache(str(tmp_path))
    evicted = cache.evict(max_mb=0.5)
    # quota pass: lattice (800KB > 512KB) drops a1; cap pass: 800KB total
    # still > 512KB, so the globally least-recently-hit survivor (b1) goes
    assert evicted == [a1, b1]
    assert os.path.isdir(a2)


def test_store_applies_env_cap_but_protects_fresh_entry(tmp_path, step_fn, step_args, monkeypatch):
    _fake_entry(tmp_path, "a" * 24, nbytes=900 * 1024, mtime=1_000)
    monkeypatch.setenv(cc.CACHE_MAX_MB_ENV_VAR, "0.2")
    executable, outcome = cc.aot_compile("step", step_fn, step_args, directory=str(tmp_path))
    assert outcome == "miss" and executable is not None
    cache = CompileCache(str(tmp_path))
    # the old oversize entry was evicted; the JUST-written one is protected
    # even though the cap is smaller than it
    assert len(cache.entries()) == 1
    assert cache.load(cc.key_from_lowered("step", step_fn.lower(*step_args))).outcome == "hit"


# ---------------------------------------------------------------------------
# kill switch + pretouch


def test_kill_switch_is_byte_identical_to_uncached(tmp_path, step_fn, step_args, monkeypatch):
    monkeypatch.setenv(cc.CACHE_ENV_VAR, "0")
    monkeypatch.setenv(cc.CACHE_DIR_ENV_VAR, str(tmp_path / "cache"))
    assert not cc.cache_enabled()
    assert cc.get_cache() is None
    executable, outcome = cc.aot_compile("step", step_fn, step_args)
    assert outcome == "uncached" and executable is not None
    loaded, key = cc.maybe_load_executable("step", step_fn, step_args)
    assert loaded is None and key is None
    assert cc.pretouch() == {"status": "disabled", "dir": None}
    # byte-identical: the configured dir was never even created
    assert not os.path.exists(tmp_path / "cache")
    np.testing.assert_array_equal(
        np.asarray(step_fn(*step_args)["w"]), np.asarray(executable(*step_args)["w"])
    )


def test_unconfigured_cache_is_inert(step_fn, step_args, monkeypatch):
    monkeypatch.delenv(cc.CACHE_DIR_ENV_VAR, raising=False)
    monkeypatch.delenv(cc.CACHE_ENV_VAR, raising=False)
    assert cc.get_cache() is None
    assert cc.pretouch() == {"status": "unconfigured", "dir": None}
    loaded, key = cc.maybe_load_executable("step", step_fn, step_args)
    assert loaded is None


def test_pretouch_statuses(tmp_path, monkeypatch):
    target = tmp_path / "cache"
    monkeypatch.setenv(cc.CACHE_DIR_ENV_VAR, str(target))
    info = cc.pretouch()
    assert info["status"] == "ok" and os.path.isdir(target)  # created = available
    # a FILE squatting on the path: cannot create the dir -> missing (visible
    # cold start), never an exception
    squatted = tmp_path / "squat"
    open(squatted, "w").write("x")
    assert cc.pretouch(directory=str(squatted))["status"] in ("missing", "readonly")
    # env-dict form (the supervisor probes the CHILD env, not its own)
    assert cc.pretouch(env={cc.CACHE_DIR_ENV_VAR: str(target)})["status"] == "ok"
    assert cc.pretouch(env={})["status"] == "unconfigured"
    assert cc.pretouch(env={cc.CACHE_ENV_VAR: "0"})["status"] == "disabled"


# ---------------------------------------------------------------------------
# cross-process key stability (the property warm restart rests on)


@pytest.mark.slow  # two subprocesses, each pays a jax import
def test_key_is_stable_across_processes():
    child = (
        "import jax, jax.numpy as jnp\n"
        "from accelerate_tpu import compile_cache as cc\n"
        "f = jax.jit(lambda p, x: {'w': p['w'] - 0.1 * (p['w'] @ x)[:, None] * x[None, :]})\n"
        "lowered = f.lower({'w': jnp.ones((8, 8))}, jnp.ones((8,)))\n"
        "print(cc.key_from_lowered('step', lowered).entry_id)\n"
    )
    ids = []
    for _ in range(2):
        res = subprocess.run(
            [sys.executable, "-c", child], env=_child_env(), capture_output=True,
            text=True, timeout=240,
        )
        assert res.returncode == 0, res.stderr[-800:]
        ids.append(res.stdout.strip().splitlines()[-1])
    assert ids[0] == ids[1] and len(ids[0]) == 24


# ---------------------------------------------------------------------------
# telemetry records + report section


def test_cache_outcomes_emit_telemetry_and_report_section(tmp_path, step_fn, step_args):
    from accelerate_tpu.telemetry import events as tel
    from accelerate_tpu.telemetry.report import (
        build_report,
        format_compile_cache_section,
        format_report,
    )

    tel_dir = tmp_path / "telemetry"
    cache_dir = tmp_path / "cache"
    tel.enable(out_dir=str(tel_dir), run_id="ccache-test")
    try:
        cc.aot_compile("step", step_fn, step_args, directory=str(cache_dir))  # miss+store
        cc.aot_compile("step", step_fn, step_args, directory=str(cache_dir))  # hit
        cache = CompileCache(str(cache_dir))
        payload = os.path.join(cache.entries()[0], cc.PAYLOAD_NAME)
        blob = bytearray(open(payload, "rb").read())
        blob[1] ^= 0xFF
        open(payload, "wb").write(bytes(blob))
        cc.aot_compile("step", step_fn, step_args, directory=str(cache_dir))  # corrupt+fallback+store
    finally:
        tel.disable()
    events = [
        json.loads(line)
        for line in open(tel_dir / "events-rank0.jsonl")
        if json.loads(line).get("kind") == "compile_cache"
    ]
    by_event = {}
    for e in events:
        by_event[e["event"]] = by_event.get(e["event"], 0) + 1
    assert by_event["miss"] == 1 and by_event["hit"] == 1
    assert by_event["corrupt"] == 1 and by_event["fallback"] == 1
    assert by_event["store"] == 2
    hit = next(e for e in events if e["event"] == "hit")
    assert hit["bytes"] > 0 and hit["load_s"] >= 0 and hit["key"]
    corrupt = next(e for e in events if e["event"] == "corrupt")
    assert "CRC32" in corrupt["reason"] and corrupt["quarantined_to"]

    report = build_report([str(tel_dir)])
    section = report["compile_cache"]
    assert section["hits"] == 1 and section["misses"] == 1
    assert section["corrupt"] == 1 and section["fallbacks"] == 1
    assert section["bytes_loaded"] > 0 and section["quarantined"]
    text = format_report(report)
    assert "compile cache:" in text and "quarantined" in text
    assert "WARNING: 1 corrupt" in format_compile_cache_section(section)


def test_disabled_telemetry_emits_nothing(tmp_path, step_fn, step_args):
    from accelerate_tpu.telemetry import events as tel

    assert not tel.is_enabled()
    cc.aot_compile("step", step_fn, step_args, directory=str(tmp_path))
    cc.aot_compile("step", step_fn, step_args, directory=str(tmp_path))
    # no telemetry dir appears anywhere under the cache dir; cache still works
    assert CompileCache(str(tmp_path)).stats()["entries"] == 1


# ---------------------------------------------------------------------------
# consumers: serving warm boot + Accelerator restart probe


@pytest.fixture(scope="module")
def tiny_engine_parts():
    from accelerate_tpu.models import init_llama
    from accelerate_tpu.models.transformer import LlamaConfig

    config = LlamaConfig(
        vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_dim=64, max_seq_len=128,
    )
    params = jax.tree_util.tree_map(
        lambda x: x.astype(np.float32), init_llama(config, jax.random.PRNGKey(0))
    )
    return config, params


def test_serving_warmup_loads_full_lattice_from_cache(tmp_path, tiny_engine_parts):
    from accelerate_tpu.serving import BucketLattice, ServingEngine

    config, params = tiny_engine_parts
    lattice = BucketLattice(slot_buckets=(1, 2), block_buckets=(4,), prefill_buckets=(16,))

    def boot():
        engine = ServingEngine(
            params, config, num_blocks=17, block_size=8, max_slots=2,
            max_blocks_per_seq=4, lattice=lattice,
            compile_cache_dir=str(tmp_path),
        )
        counts = engine.warmup()
        return engine, counts

    cold, counts_cold = boot()
    # the prefix-cache COW copy is one more warmed point (ISSUE 14)
    points = lattice.warmup_points(prefix_cache=True)
    assert cold.cache_stats["miss"] == points and cold.cache_stats["hit"] == 0
    warm, counts_warm = boot()
    # the FULL lattice loaded: every point a hit, zero compiles
    assert warm.cache_stats["hit"] == points and warm.cache_stats["miss"] == 0
    assert counts_cold == counts_warm == {
        "prefill_compiles": len(lattice.prefill_points()),
        "decode_compiles": len(lattice.decode_points()),
        "cow_compiles": 1,
    }
    # bitwise: the warm replica serves exactly what the cold one does, and
    # exactly what an uncached engine does
    prompt = (np.arange(1, 11) % 63).astype(np.int32)
    outs = []
    uncached = ServingEngine(
        params, config, num_blocks=17, block_size=8, max_slots=2,
        max_blocks_per_seq=4, lattice=lattice,
    )
    uncached.warmup()
    for engine in (cold, warm, uncached):
        req = engine.submit(prompt, 5, rng_seed=3)
        engine.run()
        outs.append(req.output_ids())
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])
    # churn after a cache-loaded warmup still never grows the caches
    assert warm.jit_cache_sizes() == counts_warm


def test_serving_warmup_with_poisoned_cache_falls_back(tmp_path, tiny_engine_parts):
    from accelerate_tpu.serving import BucketLattice, ServingEngine

    config, params = tiny_engine_parts
    lattice = BucketLattice(slot_buckets=(1,), block_buckets=(4,), prefill_buckets=(16,))

    def boot():
        engine = ServingEngine(
            params, config, num_blocks=9, block_size=8, max_slots=1,
            max_blocks_per_seq=4, lattice=lattice,
            compile_cache_dir=str(tmp_path),
        )
        engine.warmup()
        return engine

    boot()
    cache = CompileCache(str(tmp_path))
    for entry in cache.entries():
        payload = os.path.join(entry, cc.PAYLOAD_NAME)
        blob = bytearray(open(payload, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        open(payload, "wb").write(bytes(blob))
    engine = boot()  # must not crash; compiles fresh
    assert engine.cache_stats["corrupt"] == lattice.warmup_points(prefix_cache=True)
    assert cache.stats()["quarantined"] >= lattice.size()
    prompt = (np.arange(1, 9) % 63).astype(np.int32)
    req = engine.submit(prompt, 4, rng_seed=1)
    engine.run()
    assert len(req.generated) == 4


@pytest.mark.slow  # two subprocess generations, each pays a jax import + compile
def test_accelerator_restart_probe_hits_with_zero_recompiles(tmp_path):
    """The elastic-restart e2e: generation 0 trains one step (exporting via
    the perf capture), generation 1 probes the cache before tracing, runs the
    DESERIALIZED executable with zero training compiles, and produces
    bitwise-identical step output."""
    cache_dir = tmp_path / "cache"
    child = (
        "import json, os, sys\n"
        "import numpy as np\n"
        "import jax, jax.numpy as jnp\n"
        "import optax\n"
        "from accelerate_tpu import Accelerator\n"
        "from accelerate_tpu.telemetry import step_profiler as sp\n"
        "acc = Accelerator()\n"
        "params = {'w': jnp.zeros((16, 4), jnp.float32)}\n"
        "params, opt = acc.prepare(params, optax.adam(1e-2))\n"
        "def loss_fn(p, batch):\n"
        "    return jnp.mean((batch['x'] @ p['w']) ** 2)\n"
        "step = acc.prepare_train_step(loss_fn, opt)\n"
        "batch = {'x': jnp.asarray(np.ones((8, 16), np.float32))}\n"
        "c0 = sp.compile_snapshot()[0]\n"
        "params, opt_state, metrics = step(params, opt.opt_state, batch)\n"
        "params, opt_state, metrics = step(params, opt_state, batch)\n"
        "compiles = sp.compile_snapshot()[0] - c0\n"
        "print(json.dumps({'w0': float(params['w'][0, 0]), 'loss': float(metrics['loss']),\n"
        "                  'training_compiles': compiles}))\n"
        "acc.end_training()\n"
    )

    def _gen(generation):
        env = _child_env()
        env["ACCELERATE_TELEMETRY"] = "1"
        env["ACCELERATE_TELEMETRY_DIR"] = str(tmp_path / f"tel-{generation}")
        env["ACCELERATE_COMPILE_CACHE_DIR"] = str(cache_dir)
        if generation:
            env["ACCELERATE_RESTART_GENERATION"] = str(generation)
        res = subprocess.run(
            [sys.executable, "-c", child], env=env, capture_output=True,
            text=True, timeout=300,
        )
        assert res.returncode == 0, res.stderr[-1500:]
        out = json.loads(res.stdout.strip().splitlines()[-1])
        events = []
        tel_file = tmp_path / f"tel-{generation}" / "events-rank0.jsonl"
        if tel_file.exists():
            events = [json.loads(line) for line in open(tel_file)]
        out["cache_events"] = [e["event"] for e in events if e.get("kind") == "compile_cache"]
        return out

    cold = _gen(0)
    warm = _gen(1)
    assert "store" in cold["cache_events"] and "hit" not in cold["cache_events"]
    assert warm["cache_events"].count("hit") == 1
    # gen 1 ran the deserialized executable: ZERO compiles charged to training
    assert cold["training_compiles"] >= 1
    assert warm["training_compiles"] == 0
    # and the math is bitwise-identical
    assert warm["w0"] == cold["w0"] and warm["loss"] == cold["loss"]


def test_report_section_surfaces_degraded_pretouch_only(tmp_path):
    """A healthy/unconfigured supervisor pre-touch alone must NOT grow the
    report; a degraded one (missing/readonly) must render as a WARNING."""
    from accelerate_tpu.telemetry.report import build_report, format_report

    def _write(records):
        with open(tmp_path / "events-supervisor.jsonl", "w") as f:
            f.write(json.dumps({"kind": "meta", "schema": 1, "run_id": "p"}) + "\n")
            for r in records:
                f.write(json.dumps(dict(r, t=0.0)) + "\n")

    _write([{"kind": "compile_cache", "status": "unconfigured", "generation": 0}])
    report = build_report([str(tmp_path)])
    assert report["compile_cache"] is None

    _write([
        {"kind": "compile_cache", "status": "ok", "generation": 0},
        {"kind": "compile_cache", "status": "readonly", "generation": 1,
         "dir": "/shared/cache"},
    ])
    report = build_report([str(tmp_path)])
    section = report["compile_cache"]
    assert section["pretouch"] == {"ok": 1, "readonly": 1}
    text = format_report(report)
    assert "pre-touch found the cache readonly x1" in text
    assert "cold-started" in text
