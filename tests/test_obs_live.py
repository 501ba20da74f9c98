"""Live ops plane: status files, ``obs top``, validate_status wiring.

ISSUE 6 acceptance: ``obs top`` renders live state of a running
``map_batches`` with < 5% executor overhead; the status file is atomic
and schema-valid (``tools/validate_status.py`` — tier-1-wired here the
same way the other validators are).
"""

from __future__ import annotations

import importlib.util
import io
import json
import os
import statistics
import threading
import time

import numpy as np
import pytest

from tpudl import obs
from tpudl.obs import live
from tpudl.obs import watchdog as obs_watchdog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_validator():
    spec = importlib.util.spec_from_file_location(
        "validate_status",
        os.path.join(REPO, "tools", "validate_status.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def status_env(monkeypatch, tmp_path):
    """Clean writer + a tmp status dir armed via the env knob."""
    live.stop_status_writer()
    obs_watchdog.get_registry().clear()
    monkeypatch.setenv("TPUDL_STATUS_DIR", str(tmp_path))
    monkeypatch.setenv("TPUDL_STATUS_INTERVAL_S", "0.1")
    yield tmp_path
    live.stop_status_writer()
    obs_watchdog.get_registry().clear()


# -- the status file ---------------------------------------------------------

class TestStatusFile:
    def test_write_status_atomic_and_valid(self, status_env):
        from tpudl.frame import Frame

        f = Frame({"x": np.arange(512 * 4,
                                  dtype=np.float32).reshape(-1, 4)})
        f.map_batches(lambda a: a.sum(axis=1), ["x"], ["y"],
                      batch_size=32)
        # the run's heartbeat armed the daemon writer; stop it so a
        # mid-run frame of its cannot land over the explicit write
        live.stop_status_writer()
        path = live.write_status(str(status_env))
        assert path and os.path.exists(path)
        assert os.path.basename(path) == \
            f"tpudl-status-{os.getpid()}.json"
        # no tmp litter — the write is rename-into-place
        leftovers = [n for n in os.listdir(status_env) if ".tmp-" in n]
        assert leftovers == []
        vs = _load_validator()
        assert vs.validate_status(path) == []
        payload = json.load(open(path))
        assert payload["schema"] == live.SCHEMA
        run = payload["runs"][-1]
        assert run["rows_total"] == 512 and run["rows_done"] == 512
        assert run["finished"] and run["pct"] == 100.0
        assert run["config"]["batch_size"] == 32

    def test_no_dir_no_write(self, monkeypatch):
        monkeypatch.delenv("TPUDL_STATUS_DIR", raising=False)
        assert live.write_status() is None
        assert live.ensure_status_writer() is None

    def test_heartbeat_arms_writer(self, status_env):
        """Any instrumented layer registering supervised work makes the
        process monitorable — no per-layer plumbing."""
        with obs_watchdog.heartbeat("test.work", rows=10) as hb:
            hb.beat(step=1)
            deadline = time.time() + 5.0
            path = live.status_path(str(status_env))
            # the writer's first frame may be collected before the
            # heartbeat is registered; the next one (0.1 s) holds it
            payload = {"heartbeats": {}}
            while ("test.work" not in payload["heartbeats"]
                   and time.time() < deadline):
                time.sleep(0.02)
                if os.path.exists(path):
                    payload = json.load(open(path))
            assert "test.work" in payload["heartbeats"]
        live.stop_status_writer()

    def test_final_write_flips_alive(self, status_env):
        live.start_status_writer(str(status_env), interval=10.0)
        path = live.status_path(str(status_env))
        deadline = time.time() + 5.0
        while not os.path.exists(path) and time.time() < deadline:
            time.sleep(0.02)
        assert json.load(open(path))["alive"] is True
        live._atexit_stop()
        assert json.load(open(path))["alive"] is False
        live.stop_status_writer()

    def test_collect_never_raises_without_backends(self):
        payload = live.collect_status()
        assert payload["schema"] == live.SCHEMA
        assert isinstance(payload["runs"], list)


# -- live view of a RUNNING map_batches --------------------------------------

class TestLiveRun:
    def test_status_shows_in_progress_rows(self, status_env):
        """The acceptance shape: while map_batches is mid-run, the
        status file shows rows_done strictly between 0 and total, an
        unfinished run, and an ETA."""
        from tpudl.frame import Frame

        gate = threading.Event()
        seen = {"n": 0}

        def slow_fn(a):
            seen["n"] += 1
            time.sleep(0.05)        # a measurable per-batch rate
            if seen["n"] >= 4:
                gate.set()          # mid-run: some batches done
                time.sleep(0.25)    # hold the run open for the reader
            return a.sum(axis=1)

        f = Frame({"x": np.arange(64 * 16, dtype=np.float32)
                   .reshape(-1, 1)})
        t = threading.Thread(target=lambda: f.map_batches(
            slow_fn, ["x"], ["y"], batch_size=64), daemon=True)
        t.start()
        assert gate.wait(10.0)
        path = live.write_status(str(status_env))  # deterministic tick
        payload = json.load(open(path))
        running = [r for r in payload["runs"] if not r["finished"]]
        assert running, f"no in-progress run in {payload['runs']}"
        r = running[-1]
        assert 0 < r["rows_done"] < r["rows_total"] == 1024
        assert r["rows_per_sec"] and r["rows_per_sec"] > 0
        assert r["eta_s"] is not None and r["eta_s"] > 0
        t.join(15.0)
        assert not t.is_alive()

    def test_status_writer_overhead_under_5pct(self, status_env):
        """ISSUE 6 acceptance: the live monitor costs < 5% on a real
        executor run (interleaved arms + medians + absolute slack, the
        same discipline as the recorder/metrics guards)."""
        from tpudl.frame import Frame

        live.stop_status_writer()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(256, 256)).astype(np.float32)
        w = rng.normal(size=(256, 256)).astype(np.float32) * 0.05

        def fn(b):
            acc = b @ w
            for _ in range(8):
                acc = np.tanh(acc @ w)
            return acc.sum(axis=1)

        frame = Frame({"x": x})

        def run_once():
            t0 = time.perf_counter()
            frame.map_batches(fn, ["x"], ["y"], batch_size=16)
            return time.perf_counter() - t0

        run_once()  # warm caches/allocators outside the timed trials
        armed, plain = [], []
        for t in range(5):
            for arm in (("armed", "plain") if t % 2 == 0
                        else ("plain", "armed")):
                if arm == "armed":
                    live.start_status_writer(str(status_env),
                                             interval=0.05)
                    armed.append(run_once())
                else:
                    live.stop_status_writer()
                    plain.append(run_once())
        live.stop_status_writer()
        med_armed = statistics.median(armed)
        med_plain = statistics.median(plain)
        assert med_armed <= med_plain * 1.05 + 0.010, (
            f"status writer too slow: {med_armed:.4f}s vs "
            f"{med_plain:.4f}s (trials {armed} vs {plain})")


# -- ``obs top`` -------------------------------------------------------------

def _fixture_status(tmp_path, pid=4242, alive=True, with_run=True):
    payload = {
        "schema": live.SCHEMA, "version": live.VERSION,
        "ts": time.time(), "pid": pid, "host": "testhost",
        "argv": ["job.py"], "interval_s": 1.0, "alive": alive,
        "runs": [], "heartbeats": {
            "frame.map_batches": {"age_s": 0.2, "beats": 37,
                                  "info": {"stage": "dispatch"},
                                  "in_flight": {"dispatch":
                                                {"count": 1,
                                                 "age_s": 1.3}},
                                  "stalled": False}},
        "metrics": {"train.last_step": {"type": "gauge", "value": 17.0,
                                        "count": 17, "max": 17.0,
                                        "mean": 9.0}},
        "roofline": {"verdict":
                     "dispatch-bound: set fuse_steps 1→8 "
                     "(predicted +85%)",
                     "gap_attribution": {"dispatch": 0.58,
                                         "wire_h2d": 0.23,
                                         "prepare": 0.06, "d2h": 0.05,
                                         "other": 0.08}},
    }
    if with_run:
        payload["runs"] = [{
            "run_id": f"{pid}-0", "rows_total": 1024, "rows_done": 512,
            "finished": False, "wall_s": 1.15, "rows_per_sec": 445.2,
            "eta_s": 1.2, "pct": 50.0,
            "stage_seconds": {"prepare": 0.8, "dispatch": 0.9,
                              "d2h": 0.05, "infeed_wait": 0.1},
            "overlap_efficiency": 0.87, "queue_depth_mean": 1.4,
            "config": {"executor": "pipelined", "batch_size": 256,
                       "fuse_steps": 1, "prefetch_depth": 2,
                       "prepare_workers": 2, "wire_codec": "u8"},
        }]
    path = os.path.join(tmp_path, f"tpudl-status-{pid}.json")
    with open(path, "w") as f:
        json.dump(payload, f)
    return path


class TestObsTop:
    def test_render_frame_contents(self, tmp_path):
        _fixture_status(str(tmp_path))
        frame = live.render(live.read_statuses(str(tmp_path)))
        assert "pid 4242" in frame and "[live]" in frame
        assert "rows 512/1024" in frame and "(50%)" in frame
        assert "445.2 rows/s" in frame and "ETA" in frame
        assert "dispatch-bound" in frame and "fuse_steps" in frame
        assert "dispatch 58%" in frame
        assert "frame.map_batches" in frame
        assert "train.last_step 17" in frame

    def test_render_marks_stale_and_exited(self, tmp_path):
        p = _fixture_status(str(tmp_path), pid=1, alive=True)
        payload = json.load(open(p))
        payload["ts"] = time.time() - 60
        json.dump(payload, open(p, "w"))
        _fixture_status(str(tmp_path), pid=2, alive=False)
        frame = live.render(live.read_statuses(str(tmp_path)))
        assert "STALE" in frame and "EXITED" in frame

    def test_top_main_once(self, tmp_path):
        _fixture_status(str(tmp_path))
        buf = io.StringIO()
        rc = live.top_main(str(tmp_path), once=True, out=buf)
        assert rc == 0
        assert "rows 512/1024" in buf.getvalue()

    def test_top_main_once_empty_dir_rc2(self, tmp_path):
        buf = io.StringIO()
        assert live.top_main(str(tmp_path), once=True, out=buf) == 2
        assert "no tpudl-status" in buf.getvalue()

    def test_cli_e2e_once(self, tmp_path):
        """The committed CLI path: ``python -m tpudl.obs top <dir>
        --once`` over a written status file (subprocess — the real
        entry point, not the function)."""
        import subprocess
        import sys

        _fixture_status(str(tmp_path))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-m", "tpudl.obs", "top", str(tmp_path),
             "--once"],
            capture_output=True, text=True, cwd=REPO, env=env,
            timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "pid 4242" in out.stdout
        assert "dispatch-bound" in out.stdout

    def test_read_statuses_skips_torn_foreign_file(self, tmp_path):
        _fixture_status(str(tmp_path))
        with open(os.path.join(tmp_path, "tpudl-status-99.json"),
                  "w") as f:
            f.write('{"schema": "tpudl-status", "trunc')
        statuses = live.read_statuses(str(tmp_path))
        assert len(statuses) == 1 and statuses[0]["pid"] == 4242


# -- validate_status.py (tier-1 wiring) --------------------------------------

class TestValidateStatus:
    def test_valid_fixture_passes(self, tmp_path):
        vs = _load_validator()
        p = _fixture_status(str(tmp_path))
        assert vs.validate_status(p) == []
        assert vs.main(["validate_status.py", str(tmp_path)]) == 0

    def test_torn_file_is_invalid(self, tmp_path):
        vs = _load_validator()
        p = os.path.join(tmp_path, "tpudl-status-7.json")
        with open(p, "w") as f:
            f.write('{"schema": "tpudl-status", "version": 1, ')
        errs = vs.validate_status(p)
        assert errs and "torn" in errs[0]

    def test_schema_violations_flagged(self, tmp_path):
        vs = _load_validator()
        p = _fixture_status(str(tmp_path))
        payload = json.load(open(p))
        payload["runs"][0]["rows_done"] = 4096  # > rows_total
        payload["roofline"]["gap_attribution"]["dispatch"] = 7.0
        del payload["pid"]
        json.dump(payload, open(p, "w"))
        errs = vs.validate_status(p)
        assert any("rows_done" in e for e in errs)
        assert any("gap_attribution" in e for e in errs)
        assert any("missing key 'pid'" in e for e in errs)

    def test_pid_name_mismatch_flagged(self, tmp_path):
        vs = _load_validator()
        p = _fixture_status(str(tmp_path), pid=4242)
        target = os.path.join(tmp_path, "tpudl-status-13.json")
        os.rename(p, target)
        errs = vs.validate_status(target)
        assert any("filename pid" in e for e in errs)

    def test_real_writer_output_validates(self, status_env):
        """The contract the validator audits is the one the writer
        keeps — a genuine collect_status() payload passes."""
        vs = _load_validator()
        with obs_watchdog.heartbeat("validate.work"):
            path = live.write_status(str(status_env))
        assert vs.validate_status(path) == []
