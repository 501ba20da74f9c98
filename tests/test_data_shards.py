"""Shard-cache durability: checksums, atomicity, corruption recovery,
concurrent reader+writer, and the tools/validate_shards.py audit — the
tpudl.data half of the ISSUE 4 test checklist.
"""

import importlib.util
import json
import os
import threading

import numpy as np
import pytest

from tpudl.data import ShardCache, cache_key
from tpudl.data.shards import MANIFEST_NAME
from tpudl.obs import metrics as obs_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def registry():
    obs_metrics.get_registry().reset()
    yield
    obs_metrics.get_registry().reset()


@pytest.fixture(scope="module")
def validator():
    spec = importlib.util.spec_from_file_location(
        "validate_shards", os.path.join(REPO, "tools",
                                        "validate_shards.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _batch(i, rows=8):
    rng = np.random.default_rng(i)
    return [rng.integers(0, 256, size=(rows, 4, 4, 3), dtype=np.uint8),
            rng.normal(size=(rows, 5)).astype(np.float32)]


def _shard_files(cache):
    return sorted(f for f in os.listdir(cache.dir) if f.endswith(".npy"))


class TestShardCacheBasics:
    def test_put_get_roundtrip_multi_column(self, tmp_path):
        cache = ShardCache(tmp_path, cache_key("m", layout="t"))
        for i in range(3):
            cache.put(i, _batch(i))
        assert cache.indices() == [0, 1, 2]
        for i in range(3):
            got = cache.get(i)
            assert got is not None and len(got) == 2
            for a, b in zip(got, _batch(i)):
                np.testing.assert_array_equal(np.asarray(a), b)

    def test_get_is_memory_mapped(self, tmp_path):
        cache = ShardCache(tmp_path, cache_key("m"))
        cache.put(0, _batch(0))
        got = cache.get(0)
        assert isinstance(got[0], np.memmap)

    def test_miss_and_hit_counters(self, tmp_path):
        cache = ShardCache(tmp_path, cache_key("m"))
        assert cache.get(7) is None
        cache.put(7, _batch(7))
        assert cache.get(7) is not None
        snap = obs_metrics.snapshot()
        assert snap["data.cache.misses"]["value"] == 1
        assert snap["data.cache.hits"]["value"] == 1
        assert snap["data.cache.bytes_written"]["value"] > 0

    def test_distinct_keys_do_not_collide(self, tmp_path):
        a = ShardCache(tmp_path, cache_key("m", codec="u8"))
        b = ShardCache(tmp_path, cache_key("m", codec="none"))
        a.put(0, _batch(1))
        assert b.get(0) is None
        assert a.dir != b.dir

    def test_meta_persists(self, tmp_path):
        key = cache_key("m")
        ShardCache(tmp_path, key).set_meta(
            {"codecs": [["u8", 1.0, 0.0]]})
        assert ShardCache(tmp_path, key).meta == {
            "codecs": [["u8", 1.0, 0.0]]}

    def test_no_tmp_files_left_behind(self, tmp_path):
        cache = ShardCache(tmp_path, cache_key("m"))
        for i in range(4):
            cache.put(i, _batch(i))
        leftovers = [f for f in os.listdir(cache.dir) if ".tmp." in f]
        assert leftovers == []


class TestCorruptionRecovery:
    """The contract: corruption → MISS (re-prepare), never a crash."""

    def _cache_with_one(self, tmp_path):
        cache = ShardCache(tmp_path, cache_key("m"))
        cache.put(0, _batch(0))
        return cache

    def test_truncated_shard_is_a_miss(self, tmp_path):
        cache = self._cache_with_one(tmp_path)
        path = os.path.join(cache.dir, _shard_files(cache)[0])
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) // 2)
        fresh = ShardCache(tmp_path, cache.key)  # new process view
        assert fresh.get(0) is None
        assert obs_metrics.snapshot()["data.cache.corrupt"]["value"] == 1
        # re-prepare path: a fresh put over the dropped entry works
        fresh.put(0, _batch(0))
        assert fresh.get(0) is not None

    def test_bit_flip_detected_by_crc(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPUDL_DATA_VERIFY", "always")
        cache = self._cache_with_one(tmp_path)
        path = os.path.join(cache.dir, _shard_files(cache)[0])
        size = os.path.getsize(path)
        with open(path, "r+b") as f:  # flip one payload byte, same size
            f.seek(size - 1)
            byte = f.read(1)
            f.seek(size - 1)
            f.write(bytes([byte[0] ^ 0xFF]))
        assert cache.get(0) is None
        assert obs_metrics.snapshot()["data.cache.corrupt"]["value"] == 1

    def test_missing_file_is_a_miss(self, tmp_path):
        cache = self._cache_with_one(tmp_path)
        os.unlink(os.path.join(cache.dir, _shard_files(cache)[0]))
        assert cache.get(0) is None

    def test_garbage_manifest_starts_empty(self, tmp_path):
        cache = self._cache_with_one(tmp_path)
        with open(os.path.join(cache.dir, MANIFEST_NAME), "w") as f:
            f.write("{not json")
        fresh = ShardCache(tmp_path, cache.key)
        assert len(fresh) == 0  # cold, not crashed
        fresh.put(1, _batch(1))
        assert fresh.get(1) is not None

    def test_validate_reports_every_corruption(self, tmp_path):
        cache = ShardCache(tmp_path, cache_key("m"))
        for i in range(2):
            cache.put(i, _batch(i))
        assert cache.validate() == []
        files = _shard_files(cache)
        with open(os.path.join(cache.dir, files[0]), "r+b") as f:
            f.truncate(3)
        os.unlink(os.path.join(cache.dir, files[-1]))
        errs = cache.validate()
        assert any("size" in e for e in errs)
        assert any("missing" in e for e in errs)


class TestConcurrency:
    def test_concurrent_reader_and_writer(self, tmp_path):
        """One thread writes batches 0..N while another polls reads —
        every read must be None or a fully-consistent batch (atomic
        rename discipline), and the final state must be complete."""
        cache = ShardCache(tmp_path, cache_key("m"))
        n, bad = 24, []
        done = threading.Event()

        def writer():
            for i in range(n):
                cache.put(i, _batch(i))
            done.set()

        def reader():
            reader_view = ShardCache(tmp_path, cache.key)
            while not done.is_set():
                for i in range(n):
                    got = reader_view.get(i)
                    if got is None:
                        continue
                    want = _batch(i)
                    for a, b in zip(got, want):
                        if not np.array_equal(np.asarray(a), b):
                            bad.append(i)
                            return

        t_w = threading.Thread(target=writer)
        t_r = threading.Thread(target=reader)
        t_r.start(); t_w.start()
        t_w.join(); t_r.join()
        assert bad == []
        fresh = ShardCache(tmp_path, cache.key)
        assert fresh.indices() == list(range(n))
        assert fresh.validate() == []

    def test_parallel_writers_disjoint_batches(self, tmp_path):
        """Two writer threads over disjoint index sets (the prepare-pool
        shape) interleave without losing entries."""
        cache = ShardCache(tmp_path, cache_key("m"))
        ts = [threading.Thread(
            target=lambda lo=lo: [cache.put(i, _batch(i))
                                  for i in range(lo, 16, 2)])
            for lo in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert cache.indices() == list(range(16))
        assert cache.validate() == []


class TestValidateShardsTool:
    """tools/validate_shards.py is the offline audit authority — wired
    into tier-1 here exactly like tools/validate_metrics.py is in
    test_obs_metrics.py."""

    def test_clean_cache_validates(self, tmp_path, validator):
        cache = ShardCache(tmp_path, cache_key("m"))
        for i in range(3):
            cache.put(i, _batch(i))
        cache.set_meta({"codecs": [["u8", 1.0, 0.0], ["identity"]]})
        errs, n_manifests, n_files = validator.validate_cache_dir(
            str(tmp_path))
        assert errs == [] and n_manifests == 1 and n_files == 6
        # key-dir direct path too
        errs, _, _ = validator.validate_cache_dir(cache.dir)
        assert errs == []

    def test_corrupted_cache_fails_audit(self, tmp_path, validator):
        cache = ShardCache(tmp_path, cache_key("m"))
        cache.put(0, _batch(0))
        files = _shard_files(cache)
        path = os.path.join(cache.dir, files[0])
        size = os.path.getsize(path)
        with open(path, "r+b") as f:  # same-size bit flip → crc catches
            f.seek(size - 1)
            b = f.read(1)
            f.seek(size - 1)
            f.write(bytes([b[0] ^ 0xFF]))
        errs, _, _ = validator.validate_cache_dir(str(tmp_path))
        assert any("crc32 mismatch" in e for e in errs)

    def test_schema_violations_reported(self, tmp_path, validator):
        cache = ShardCache(tmp_path, cache_key("m"))
        cache.put(0, _batch(0))
        mpath = os.path.join(cache.dir, MANIFEST_NAME)
        with open(mpath) as f:
            m = json.load(f)
        del m["shards"]["0"]["files"][0]["crc32"]
        m["shards"]["x"] = {"files": []}
        with open(mpath, "w") as f:
            json.dump(m, f)
        errs, _, _ = validator.validate_cache_dir(str(tmp_path))
        assert any("crc32" in e and "missing" in e for e in errs)
        assert any("non-integer" in e for e in errs)

    def test_cli_exit_codes(self, tmp_path, validator, capsys):
        assert validator.main(["v"]) == 2
        cache = ShardCache(tmp_path, cache_key("m"))
        cache.put(0, _batch(0))
        assert validator.main(["v", str(tmp_path)]) == 0
        with open(os.path.join(cache.dir, _shard_files(cache)[0]),
                  "r+b") as f:
            f.truncate(1)
        assert validator.main(["v", str(tmp_path)]) == 1
        out = capsys.readouterr()
        assert "INVALID" in out.err
