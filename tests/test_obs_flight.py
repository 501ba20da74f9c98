"""Failure-forensics tests (ISSUE 5): flight recorder rings + dump
round-trips (exception / SIGTERM / faulthandler), stall-watchdog
detection on a synthetic frozen stage, the deliberately-stalled
``map_batches`` → dump → ``obs doctor`` acceptance path, doctor CLI
e2e on synthetic single- and multi-host fixtures, restart forensics,
``tools/validate_dump.py`` (tier-1 wiring), and what the recorder and
the watchdog leave behind after a healthy run."""

import gzip
import importlib.util
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from tpudl import obs
from tpudl.obs import doctor as obs_doctor
from tpudl.obs import flight
from tpudl.obs import watchdog as obs_watchdog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_validator():
    spec = importlib.util.spec_from_file_location(
        "validate_dump", os.path.join(REPO, "tools", "validate_dump.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def forensics(monkeypatch, tmp_path):
    """Clean recorder + registry + watchdog, dumps into tmp_path."""
    monkeypatch.setenv("TPUDL_FLIGHT_DIR", str(tmp_path))
    monkeypatch.delenv("TPUDL_WATCHDOG_STALL_S", raising=False)
    obs_watchdog.stop_watchdog()
    obs_watchdog.get_registry().clear()
    rec = flight.get_recorder()
    rec.reset()
    obs.get_registry().reset()
    yield rec
    obs_watchdog.stop_watchdog()
    obs_watchdog.get_registry().clear()
    rec.reset()
    obs.get_registry().reset()


# -- recorder rings --------------------------------------------------------
class TestFlightRecorder:
    def test_rings_stay_bounded(self, forensics):
        for i in range(200):
            forensics.record_batch("prepare", i,
                                   [np.zeros((2, 2), np.float32)])
            forensics.record_error("k", ValueError(f"e{i}"))
            forensics.record_restart(i, RuntimeError("r"), step=i)
        snap = forensics.snapshot()
        assert len(snap["batches"]) <= 4096
        assert len(snap["batches"]) == forensics._batches.maxlen
        assert len(snap["errors"]) == forensics._errors.maxlen
        assert len(snap["restarts"]) <= 64  # crash-loop bounded

    def test_batch_descriptor_never_holds_data(self, forensics):
        arr = np.arange(4096, dtype=np.float32).reshape(64, 64)
        forensics.record_batch("prepare", 0, [arr], rows=64)
        desc = forensics.snapshot()["batches"][0]
        assert desc["shapes"] == [[64, 64]]
        assert desc["dtypes"] == ["float32"]
        assert isinstance(desc["fingerprint"], str)
        # the whole descriptor serializes tiny — no pixel payload
        assert len(json.dumps(desc)) < 500

    def test_fingerprint_distinguishes_content(self, forensics):
        a = np.zeros((8, 8), np.float32)
        b = np.ones((8, 8), np.float32)
        fa = flight.batch_fingerprint([a])
        fb = flight.batch_fingerprint([b])
        assert fa is not None and fa != fb
        assert flight.batch_fingerprint([a.copy()]) == fa
        # object columns can't expose raw bytes: None, not a crash
        obj = np.empty(2, dtype=object)
        obj[:] = [b"x", b"y"]
        assert flight.batch_fingerprint([obj]) is None
        # a non-contiguous view (strided pack output) samples via the
        # flat iterator — same logical content, same fingerprint, and
        # crucially NO whole-array copy on the hot path
        base = np.arange(64, dtype=np.float32).reshape(8, 8)
        assert flight.batch_fingerprint([base.T]) == \
            flight.batch_fingerprint([np.ascontiguousarray(base.T)])

    def test_dump_roundtrip_schema_valid(self, forensics, tmp_path):
        forensics.record_batch("prepare", 0,
                               [np.zeros((4, 3), np.float32)])
        forensics.record_error("imageio.decode_error",
                               ValueError("bad jpeg"), origin="x.jpg")
        path = obs.dump(reason="manual")
        assert path and os.path.exists(path)
        assert os.path.basename(path) == f"tpudl-dump-{os.getpid()}.json.gz"
        with gzip.open(path, "rt") as f:
            payload = json.load(f)
        assert payload["schema"] == "tpudl-flight-dump"
        assert payload["reason"] == "manual"
        assert payload["pid"] == os.getpid()
        vd = _load_validator()
        assert vd.validate_dump(path) == []
        # atomic write: no tmp litter next to the dump
        assert not [p for p in os.listdir(tmp_path) if ".tmp." in p]

    def test_timeout_dump_gives_up_instead_of_deadlocking(self,
                                                          forensics):
        """Signal-context contract: if the interrupted frame holds the
        recorder lock, dump(timeout=...) must return None promptly —
        never block the handler forever (whatever the process writes
        on SIGTERM depends on the handler finishing)."""
        forensics._lock.acquire()  # simulate the interrupted holder
        try:
            t0 = time.monotonic()
            assert forensics.dump(reason="signal:15",
                                  timeout=0.3) is None
            assert time.monotonic() - t0 < 3.0
        finally:
            forensics._lock.release()
        # unblocked path still works
        assert forensics.dump(reason="manual", timeout=5.0) is not None

    def test_dump_env_is_filtered(self, forensics, monkeypatch):
        monkeypatch.setenv("TPUDL_SECRETLESS_KNOB", "1")
        monkeypatch.setenv("AWS_SECRET_ACCESS_KEY", "hunter2")
        path = obs.dump()
        with gzip.open(path, "rt") as f:
            env = json.load(f)["env"]
        assert "TPUDL_SECRETLESS_KNOB" in env
        assert "AWS_SECRET_ACCESS_KEY" not in env


# -- automatic triggers (subprocess round-trips) ---------------------------
def _run_child(tmp_path, body, env_extra=None, sig=None, timeout=60):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               TPUDL_FLIGHT_DIR=str(tmp_path), **(env_extra or {}))
    proc = subprocess.Popen([sys.executable, "-c", body],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=env)
    if sig is not None:
        # wait for the child to report installed handlers before killing
        line = proc.stdout.readline()
        assert "READY" in line, (line, proc.stderr.read())
        proc.send_signal(sig)
    out, err = proc.communicate(timeout=timeout)
    return proc.returncode, out, err


def test_dump_never_brings_a_backend_up():
    """``import tpudl`` loads jax; a flight dump from a process that
    meant to stay off the device (a parent before the children that
    need the chip, a dying interpreter) must not initialise a backend — on a
    TPU host the first ``jax.device_count()`` TAKES the chip."""
    import subprocess
    import sys

    code = (
        "import tpudl\n"
        "from tpudl.obs import flight\n"
        "from jax._src import xla_bridge as xb\n"
        "snap = flight.get_recorder().snapshot('probe')\n"
        "assert snap['backend'] == {'jax_loaded': True, "
        "'backend_up': False}, snap['backend']\n"
        "assert not xb.backends_are_initialized()\n"
        "import jax; jax.devices()\n"
        "assert flight._jax_info()['device_count'] >= 1\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-1500:]


class TestDumpTriggers:
    def test_unhandled_exception_dumps(self, forensics, tmp_path):
        rc, _out, err = _run_child(tmp_path, (
            "from tpudl.obs import flight\n"
            "flight.install()\n"
            "raise RuntimeError('boom for forensics')\n"))
        assert rc == 1
        assert "boom for forensics" in err  # prior excepthook chained
        dumps = obs_doctor.load_dumps(str(tmp_path))
        assert len(dumps) == 1
        d = dumps[0]
        assert d["reason"] == "exception"
        assert d["error"]["type"] == "RuntimeError"
        assert "boom for forensics" in d["error"]["message"]
        vd = _load_validator()
        errs, n = vd.validate_path(str(tmp_path))
        assert errs == [] and n == 1

    def test_sigterm_dumps_and_preserves_exit(self, forensics, tmp_path):
        rc, _out, _err = _run_child(tmp_path, (
            "import time\n"
            "from tpudl.obs import flight\n"
            "flight.install()\n"
            "print('READY', flush=True)\n"
            "time.sleep(30)\n"), sig=signal.SIGTERM)
        # default disposition preserved: died OF SIGTERM, not exit(0)
        assert rc == -signal.SIGTERM
        dumps = obs_doctor.load_dumps(str(tmp_path))
        assert len(dumps) == 1
        assert dumps[0]["reason"] == f"signal:{int(signal.SIGTERM)}"
        vd = _load_validator()
        errs, _n = vd.validate_path(str(tmp_path))
        assert errs == []

    def test_prior_python_sigterm_handler_chained(self, forensics,
                                                  tmp_path):
        marker = tmp_path / "prior_handler_ran"
        rc, _out, _err = _run_child(tmp_path, (
            "import os, signal, sys, time\n"
            f"mk = {str(marker)!r}\n"
            "def prior(signum, frame):\n"
            "    open(mk, 'w').write('yes')\n"
            "    sys.exit(3)\n"
            "signal.signal(signal.SIGTERM, prior)\n"
            "from tpudl.obs import flight\n"
            "flight.install()\n"
            "print('READY', flush=True)\n"
            "time.sleep(30)\n"), sig=signal.SIGTERM)
        assert rc == 3  # the user's handler still decided the exit
        assert marker.exists()
        assert len(obs_doctor.load_dumps(str(tmp_path))) == 1

    def test_faulthandler_optin_covers_native_crash(self, forensics,
                                                    tmp_path):
        rc, _out, _err = _run_child(tmp_path, (
            "import faulthandler\n"
            "from tpudl.obs import flight\n"
            "flight.install()\n"
            "faulthandler._sigsegv()\n"),
            env_extra={"TPUDL_FAULTHANDLER": "1"})
        assert rc == -signal.SIGSEGV
        logs = [p for p in os.listdir(tmp_path)
                if p.startswith("tpudl-fault-")]
        assert len(logs) == 1
        text = (tmp_path / logs[0]).read_text()
        assert "Segmentation fault" in text or "Current thread" in text


# -- watchdog --------------------------------------------------------------
class TestWatchdog:
    def test_synthetic_frozen_stage_flags_once(self, forensics):
        wd = obs_watchdog.Watchdog(obs_watchdog.get_registry(),
                                   stall_s=0.05)
        with obs_watchdog.heartbeat("synthetic.run",
                                    stage="prepare") as hb:
            hb.beat(stage="prepare")
            time.sleep(0.12)  # frozen past the threshold
            flagged = wd.scan()
            assert len(flagged) == 1
            ev = flagged[0]
            assert ev["name"] == "synthetic.run"
            assert ev["info"]["stage"] == "prepare"
            assert ev["age_s"] > 0.05
            # every thread's stack is in the event (this one included)
            assert any("test_obs_flight" in "".join(stack)
                       for stack in ev["stacks"].values())
            # one event per episode: a second scan stays quiet
            assert wd.scan() == []
            # a beat re-arms the episode
            hb.beat(stage="dispatch")
            time.sleep(0.12)
            again = wd.scan()
            assert len(again) == 1
            assert again[0]["info"]["stage"] == "dispatch"
        s = obs.snapshot()
        assert s["obs.watchdog.stalls"]["value"] == 2.0
        assert len(forensics.snapshot()["stalls"]) == 2

    def test_wedged_dispatch_not_blamed_on_prepare(self, forensics):
        """Attribution: a dispatch that freezes while prepare workers
        finish their in-flight batches (and beat afterwards) must stay
        the suspect — the in-flight stage set survives later beats."""
        wd = obs_watchdog.Watchdog(obs_watchdog.get_registry(),
                                   stall_s=0.05)
        with obs_watchdog.heartbeat("frame.map_batches") as hb:
            hb.stage_enter("dispatch")   # consumer wedges in here
            hb.stage_enter("prepare")    # a worker still finishes one
            hb.stage_exit("prepare")     # ...beating AFTER the wedge
            time.sleep(0.12)
            flagged = wd.scan()
            assert len(flagged) == 1
            ev = flagged[0]
            assert list(ev["in_flight"]) == ["dispatch"]
            # the doctor reads the in-flight stage, not the last beat
            assert obs_doctor._stall_stage(ev) == "dispatch"
            hb.stage_exit("dispatch")
        p = obs.dump(reason="manual")
        _merged, diag = obs_doctor.diagnose(p)
        assert diag["classification"] == "dispatch_slowdown"
        assert diag["suspect_stage"] == "dispatch"

    def test_child_beats_rearm_parent_heartbeat(self, forensics):
        """A coarse outer heartbeat (UDF call, HPO trial) with one beat
        per invocation must not false-flag while its inner executor/
        trainer heartbeats are making progress."""
        wd = obs_watchdog.Watchdog(obs_watchdog.get_registry(),
                                   stall_s=0.08)
        with obs_watchdog.heartbeat("hpo.trial", index=0):
            time.sleep(0.1)  # outer past the threshold on its own...
            with obs_watchdog.heartbeat("frame.map_batches") as inner:
                inner.beat(stage="prepare")  # ...but the child beats
                assert wd.scan() == []
                time.sleep(0.1)  # BOTH silent now: the outer flags
                flagged = wd.scan()
            assert {e["name"] for e in flagged} == {"hpo.trial",
                                                   "frame.map_batches"}

    def test_finished_work_never_flags(self, forensics):
        wd = obs_watchdog.Watchdog(obs_watchdog.get_registry(),
                                   stall_s=0.01)
        with obs_watchdog.heartbeat("quick.run") as hb:
            hb.beat()
        time.sleep(0.05)
        assert wd.scan() == []  # deregistered on exit
        assert obs_watchdog.get_registry().describe() == {}

    def test_supervised_retry_is_not_double_flagged_as_stall(
            self, forensics, monkeypatch):
        """ISSUE 14 satellite: a stage the fault-containment supervisor
        is actively retrying must not be flagged as a stall — each
        retry attempt registers a FRESH executor heartbeat, and the
        supervisor's own heartbeat is beaten through every rung and
        every backoff slice, so even a retry pause longer than
        TPUDL_WATCHDOG_STALL_S stays un-flagged while a genuinely hung
        run still would be."""
        from tpudl.frame import Frame
        from tpudl.testing import faults

        # retry backoff (0.3s) deliberately LONGER than the stall
        # threshold (0.12s): without the re-arm this is a guaranteed
        # false stall
        monkeypatch.setenv("TPUDL_RETRY_IO_BACKOFF_S", "0.3")
        obs_watchdog.start_watchdog(stall_s=0.12, interval=0.04)
        frame = Frame({"x": np.arange(64, dtype=np.float32)})
        plan = faults.FaultPlan(
            [{"point": "frame.prepare", "action": "raise",
              "exc": "OSError", "first_calls": 1}])
        with plan.armed():
            out = frame.map_batches(lambda b: b * 2, ["x"], ["y"],
                                    batch_size=16, supervise=True)
        assert np.array_equal(np.asarray(out["y"]),
                              np.arange(64, dtype=np.float32) * 2)
        assert plan.fired, "the retry path must actually have run"
        time.sleep(0.1)  # let a final scan pass over the (empty) set
        assert "obs.watchdog.stalls" not in obs.snapshot(), (
            "a supervised retry was double-flagged as a stall")
        assert forensics.snapshot()["stalls"] == []

    def test_daemon_thread_detects_stall(self, forensics):
        obs_watchdog.start_watchdog(stall_s=0.1, interval=0.03)
        with obs_watchdog.heartbeat("daemon.victim", stage="h2d"):
            time.sleep(0.4)
        assert obs.snapshot()["obs.watchdog.stalls"]["value"] >= 1.0
        stalls = forensics.snapshot()["stalls"]
        assert stalls and stalls[0]["name"] == "daemon.victim"
        # the scan cadence also feeds the metric-tick ring
        assert forensics.snapshot()["metric_ticks"]

    def test_env_autostarts_daemon(self, forensics, monkeypatch):
        monkeypatch.setenv("TPUDL_WATCHDOG_STALL_S", "0.1")
        with obs_watchdog.heartbeat("auto.victim", stage="prepare"):
            time.sleep(0.35)
        assert obs.snapshot()["obs.watchdog.stalls"]["value"] >= 1.0


# -- acceptance: stalled executor → dump → doctor --------------------------
class TestExecutorForensics:
    def test_map_batches_records_batch_descriptors(self, forensics):
        from tpudl.frame import Frame

        x = np.arange(32, dtype=np.float32)
        Frame({"x": x}).map_batches(lambda b: b * 2, ["x"], ["y"],
                                    batch_size=8)
        batches = forensics.snapshot()["batches"]
        assert len(batches) == 4
        assert all(b["stage"] == "prepare" for b in batches)
        assert batches[0]["shapes"] == [[8]]
        # the run's heartbeat deregistered on the happy path
        assert obs_watchdog.get_registry().describe() == {}

    def test_stalled_map_batches_dump_classifies_infeed(self, forensics):
        """ISSUE 5 acceptance: a deliberately stalled ``map_batches``
        run produces a dump that ``obs doctor`` classifies as an
        infeed stall naming the frozen stage."""
        from tpudl.frame import Frame

        wd = obs_watchdog.Watchdog(obs_watchdog.get_registry(),
                                   stall_s=0.15)
        frozen = threading.Event()
        release = threading.Event()

        def stalling_pack(sl):
            if not frozen.is_set():
                frozen.set()
                release.wait(timeout=10)  # the deliberate freeze
            return np.asarray(sl)

        stalling_pack.thread_safe = True
        x = np.arange(64, dtype=np.float32)

        def run():
            Frame({"x": x}).map_batches(lambda b: b + 1, ["x"], ["y"],
                                        batch_size=16,
                                        pack=stalling_pack)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        assert frozen.wait(timeout=10)
        time.sleep(0.2)  # let the freeze age past stall_s
        flagged = wd.scan()  # deterministic: drive the scan directly
        release.set()
        t.join(timeout=10)
        assert flagged and flagged[0]["name"] == "frame.map_batches"

        dump_path = obs.dump(reason="manual")
        got = obs_doctor.diagnose(dump_path)
        assert got is not None
        merged, diagnosis = got
        assert diagnosis["classification"] == "infeed_stall"
        assert diagnosis["suspect_stage"] == "prepare"
        report = obs_doctor.format_report(merged, diagnosis)
        assert "infeed_stall" in report and "prepare" in report

    def test_estimator_heartbeat_registers(self, forensics):
        # the estimator's train loop is supervised (unit-level: the
        # heartbeat API it uses is the registry's)
        with obs_watchdog.heartbeat("estimator.train_trial",
                                    epochs=1) as hb:
            hb.beat(epoch=0, step=0)
            desc = obs_watchdog.get_registry().describe()
            assert desc["estimator.train_trial"]["info"]["step"] == 0


# -- doctor classification on synthetic fixtures ---------------------------
def _payload(**over):
    base = {"schema": "tpudl-flight-dump", "version": 1,
            "reason": "manual", "ts": time.time(), "pid": 1000,
            "process_index": 0, "process_count": 1, "argv": ["job.py"],
            "python": "3.11.0", "backend": {"jax_loaded": False},
            "env": {}, "error": None, "batches": [], "errors": [],
            "stalls": [], "metric_ticks": [], "restarts": [],
            "events": [], "metrics": {}, "pipeline_reports": {},
            "spans": [], "heartbeats": {}}
    base.update(over)
    return base


def _stall(stage, name="frame.map_batches", age=12.0):
    return {"ts": time.time(), "name": name, "info": {"stage": stage},
            "beats": 5, "age_s": age, "stall_s": 5.0, "active": [name],
            "stacks": {"1:MainThread": ["  File x, line 1"]}}


def _counter(v):
    return {"type": "counter", "value": float(v)}


def _write_dump(path, payload):
    with gzip.open(path, "wt", encoding="utf-8") as f:
        json.dump(payload, f)
    return str(path)


class TestDoctor:
    def test_decode_error_storm(self, tmp_path):
        p = _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="exception",
            error={"type": "RuntimeError", "message": "batch empty"},
            metrics={"imageio.decode_errors": _counter(40),
                     "imageio.files_read": _counter(100)},
            errors=[{"ts": 1.0, "kind": "imageio.decode_error",
                     "type": "ValueError", "message": "bad jpeg",
                     "origin": f"f{i}.jpg"} for i in range(5)]))
        merged, diag = obs_doctor.diagnose(p)
        # the storm outranks the exception it caused
        assert diag["classification"] == "decode_error_storm"
        assert diag["suspect_stage"] == "decode"

    def test_isolated_corruption_is_not_a_storm(self, tmp_path):
        p = _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="signal:15",
            metrics={"imageio.decode_errors": _counter(1),
                     "imageio.files_read": _counter(5000)}))
        _merged, diag = obs_doctor.diagnose(p)
        assert diag["classification"] == "clean_external_kill"

    def test_dispatch_stall(self, tmp_path):
        p = _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="signal:15", stalls=[_stall("dispatch")]))
        _merged, diag = obs_doctor.diagnose(p)
        assert diag["classification"] == "dispatch_slowdown"
        assert diag["suspect_stage"] == "dispatch"

    def test_clean_external_kill(self, tmp_path):
        p = _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="signal:15",
            pipeline_reports={"1000-0": {
                "run_id": "1000-0", "wall_seconds": 10.0,
                "stage_seconds": {"prepare": 4.0, "dispatch": 5.0},
                "stage_calls": {"prepare": 40, "dispatch": 40}}}))
        _merged, diag = obs_doctor.diagnose(p)
        assert diag["classification"] == "clean_external_kill"
        # per-stage throughput at death is in the diagnosis
        assert diag["stage_rates"]["dispatch"]["calls"] == 40

    def test_exception_passthrough(self, tmp_path):
        p = _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="exception",
            error={"type": "KeyError", "message": "'label'"}))
        _merged, diag = obs_doctor.diagnose(p)
        assert diag["classification"] == "exception"
        assert "'label'" in diag["evidence"][0]

    def test_multi_host_merge_names_suspect_host(self, tmp_path):
        _write_dump(tmp_path / "tpudl-dump-host0-1.json.gz", _payload(
            reason="signal:15", process_index=0, process_count=2,
            spans=[{"name": "frame.dispatch", "ts_us": 2e12,
                    "dur_us": 100.0, "tid": 1, "thread": "Main",
                    "attrs": None}]))
        _write_dump(tmp_path / "tpudl-dump-host1-2.json.gz", _payload(
            reason="signal:15", process_index=1, process_count=2,
            pid=2000, stalls=[_stall("prepare")],
            spans=[{"name": "frame.prepare", "ts_us": 2.1e12,
                    "dur_us": 900.0, "tid": 2, "thread": "Main",
                    "attrs": None}]))
        merged, diag = obs_doctor.diagnose(str(tmp_path))
        assert merged["n_hosts"] == 2
        assert diag["classification"] == "infeed_stall"
        assert diag["suspect_host"] == "1"
        # merged timeline tail interleaves hosts by wall clock
        assert [s["host"] for s in merged["spans"]] == ["0", "1"]

    def test_multi_host_stalls_merge_in_time_order(self, tmp_path):
        """'The last stall' must be the NEWEST across hosts, not
        whichever host's dump iterated last."""
        old = _stall("prepare")
        old["ts"] = 100.0
        new = _stall("dispatch")
        new["ts"] = 200.0
        _write_dump(tmp_path / "tpudl-dump-host0-1.json.gz", _payload(
            process_index=0, process_count=2, stalls=[new]))
        _write_dump(tmp_path / "tpudl-dump-host1-2.json.gz", _payload(
            process_index=1, process_count=2, pid=2000, stalls=[old]))
        merged, diag = obs_doctor.diagnose(str(tmp_path))
        assert [s["ts"] for s in merged["stalls"]] == [100.0, 200.0]
        assert diag["classification"] == "dispatch_slowdown"
        assert diag["suspect_host"] == "0"

    def test_same_index_distinct_pids_both_kept(self, tmp_path):
        """A parent and its trial subprocess share process_index
        0 in one dump dir — the child's stall evidence must survive
        the merge (dedup is per (index, pid), not per index)."""
        child = _payload(pid=2001, ts=time.time() - 10,
                         stalls=[_stall("prepare")])
        parent = _payload(pid=2000, reason="deadline")
        _write_dump(tmp_path / "tpudl-dump-2001.json.gz", child)
        _write_dump(tmp_path / "tpudl-dump-2000.json.gz", parent)
        merged, diag = obs_doctor.diagnose(str(tmp_path))
        assert merged["n_hosts"] == 2  # "0:2000" and "0:2001"
        assert diag["classification"] == "infeed_stall"
        assert diag["suspect_stage"] == "prepare"

    def test_unattributed_stall_is_honest(self, tmp_path):
        """A frozen train step / UDF call carries no stage info: the
        doctor must say 'stall' and point at the stacks, not guess
        dispatch_slowdown."""
        ev = _stall(None, name="train.fit")
        ev["info"] = {"step": 17}
        p = _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="signal:15", stalls=[ev]))
        _merged, diag = obs_doctor.diagnose(p)
        assert diag["classification"] == "stall"
        assert diag["suspect_stage"] is None

    def test_cli_e2e_single_and_multi_host(self, tmp_path, capsys):
        from tpudl.obs.__main__ import main as obs_main

        single = tmp_path / "single"
        single.mkdir()
        _write_dump(single / "tpudl-dump-1000.json.gz", _payload(
            reason="signal:15"))
        assert obs_main(["doctor", str(single)]) == 0
        out = capsys.readouterr().out
        assert "clean_external_kill" in out

        multi = tmp_path / "multi"
        multi.mkdir()
        _write_dump(multi / "tpudl-dump-host0-1.json.gz", _payload(
            process_index=0, process_count=2, reason="signal:15"))
        _write_dump(multi / "tpudl-dump-host1-2.json.gz", _payload(
            process_index=1, process_count=2, reason="signal:15",
            stalls=[_stall("h2d")]))
        assert obs_main(["doctor", str(multi)]) == 0
        out = capsys.readouterr().out
        assert "2 host dump(s)" in out
        assert "infeed_stall" in out and "h2d" in out

    def test_cli_no_dumps_rc2(self, tmp_path, capsys):
        from tpudl.obs.__main__ import main as obs_main

        assert obs_main(["doctor", str(tmp_path)]) == 2

    def test_overload_shed_single_host(self, tmp_path):
        """A death under sustained typed rejects classifies as
        capacity, not as a bug hunt: the serve plane was ANSWERING."""
        p = _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="signal:15",
            metrics={"serve.rejects": _counter(30),
                     "serve.requests": _counter(200),
                     "serve.deadline_sheds": _counter(4),
                     "serve.queue_depth": {"type": "gauge",
                                           "value": 64.0},
                     "serve.queue_cap": {"type": "gauge",
                                         "value": 64.0}}))
        _merged, diag = obs_doctor.diagnose(p)
        assert diag["classification"] == "overload_shed"
        assert diag["suspect_stage"] == "admission"
        assert any("30 of 230" in e and "13%" in e
                   for e in diag["evidence"])
        assert any("depth 64 of cap 64" in e for e in diag["evidence"])
        assert any("4 request(s) shed on expired deadlines" in e
                   for e in diag["evidence"])
        assert any("TPUDL_SERVE_QUEUE_CAP" in e
                   for e in diag["evidence"])

    def test_overload_shed_multi_host_names_shedding_host(self,
                                                          tmp_path):
        _write_dump(tmp_path / "tpudl-dump-host0-1.json.gz", _payload(
            reason="signal:15", process_index=0, process_count=2,
            metrics={"serve.requests": _counter(100)}))
        _write_dump(tmp_path / "tpudl-dump-host1-2.json.gz", _payload(
            reason="signal:15", process_index=1, process_count=2,
            pid=2000,
            metrics={"serve.rejects": _counter(25),
                     "serve.requests": _counter(80)}))
        merged, diag = obs_doctor.diagnose(str(tmp_path))
        assert merged["n_hosts"] == 2
        assert diag["classification"] == "overload_shed"
        assert diag["suspect_host"] == "1"

    def test_few_rejects_are_not_overload(self, tmp_path):
        """Below the sustained bar (>= 8 rejects AND >= 10% of offered
        load) a handful of rejects must not reroute an unrelated
        death."""
        p = _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="signal:15",
            metrics={"serve.rejects": _counter(3),
                     "serve.requests": _counter(10)}))
        _merged, diag = obs_doctor.diagnose(p)
        assert diag["classification"] == "clean_external_kill"

    def test_degraded_run_outranks_overload_shed(self, tmp_path):
        """A mid-ladder death is the degradation story even when the
        serve plane was also shedding — the rung trail explains WHY
        admission was drowning."""
        p = _write_dump(tmp_path / "tpudl-dump-1000.json.gz", _payload(
            reason="degraded_exhausted",
            metrics={"frame.degraded.rungs": _counter(2),
                     "serve.rejects": _counter(30),
                     "serve.requests": _counter(100)}))
        _merged, diag = obs_doctor.diagnose(p)
        assert diag["classification"] == "degraded_run"


# -- restart forensics -----------------------------------------------------
class TestRestartForensics:
    def test_runner_records_restart_cause_and_step(self, forensics):
        from tpudl.train import HorovodRunner

        state = {"tries": 0}

        def main(ctx):
            state["tries"] += 1
            if state["tries"] == 1:
                raise RuntimeError("nan loss at step 7")
            return "ok"

        result = HorovodRunner(np=1, max_restarts=1).run(main)
        assert result == "ok"
        restarts = forensics.snapshot()["restarts"]
        assert len(restarts) == 1
        assert restarts[0]["attempt"] == 1
        assert restarts[0]["error_type"] == "RuntimeError"
        assert "nan loss at step 7" in restarts[0]["error"]
        assert "nan loss" in restarts[0]["traceback"]

    def test_exhaustion_records_error_ring(self, forensics):
        from tpudl.train import HorovodRunner, RestartsExhausted

        def always_fails(ctx):
            raise ValueError("poisoned batch")

        try:
            # budget exhaustion raises the TYPED RestartsExhausted
            # carrying the last cause (the jobs-runtime contract)
            with pytest.raises(RestartsExhausted,
                               match="poisoned batch") as ei:
                HorovodRunner(np=1, max_restarts=1).run(always_fails)
        except AttributeError as e:
            pytest.skip(f"mesh API unavailable in this jax: {e}")
        assert isinstance(ei.value.last_cause, ValueError)
        snap = forensics.snapshot()
        assert len(snap["restarts"]) == 2  # both attempts recorded
        kinds = [e["kind"] for e in snap["errors"]]
        assert "train.exhausted" in kinds

    def test_trainer_step_heartbeat_and_last_step(self, forensics):
        optax = pytest.importorskip("optax")

        import jax.numpy as jnp

        from tpudl.train import Trainer

        def loss_fn(p, x, y):
            return jnp.mean((x @ p["w"] - y) ** 2)

        X = np.ones((8, 4), np.float32)
        Y = np.ones((8, 1), np.float32)
        Trainer(loss_fn, optax.sgd(0.1)).fit(
            {"w": jnp.zeros((4, 1))}, lambda s: (X, Y), steps=3)
        assert obs.snapshot()["train.last_step"]["value"] == 3.0
        assert obs_watchdog.get_registry().describe() == {}


# -- validate_dump.py ------------------------------------------------------
class TestValidateDump:
    def test_rejects_missing_keys_and_ring_overflow(self, tmp_path):
        vd = _load_validator()
        bad = _payload()
        del bad["stalls"]
        bad["errors"] = [{"ts": 1.0, "kind": "k",
                          "message": "m"}] * 5000  # past any bound
        p = _write_dump(tmp_path / "tpudl-dump-9.json.gz", bad)
        errs = vd.validate_dump(p)
        assert any("missing key 'stalls'" in e for e in errs)
        assert any("ring 'errors'" in e for e in errs)

    def test_rejects_data_leak_in_descriptor(self, tmp_path):
        vd = _load_validator()
        leak = _payload(batches=[{
            "ts": 1.0, "stage": "prepare", "index": 0,
            "shapes": [[64, 64]], "dtypes": ["float32"],
            "pixels": list(range(999))}])  # the forbidden payload
        p = _write_dump(tmp_path / "tpudl-dump-9.json.gz", leak)
        errs = vd.validate_dump(p)
        assert any("must not carry data" in e for e in errs)

    def test_unreadable_file_reported(self, tmp_path):
        vd = _load_validator()
        p = tmp_path / "tpudl-dump-9.json.gz"
        p.write_bytes(b"not gzip at all")
        assert any("unreadable" in e for e in vd.validate_dump(str(p)))

    def test_cli_ok_on_real_dump(self, forensics, tmp_path):
        obs.dump(reason="manual")
        vd = _load_validator()
        assert vd.main(["validate_dump.py", str(tmp_path)]) == 0


# -- what the recorder and the watchdog do to a healthy run (acceptance) ----
def test_recorder_watchdog_healthy_run_records_one_descriptor_a_batch(
        forensics, tmp_path):
    """ISSUE 5 acceptance, as counts: with the recorder on and the
    watchdog daemon scanning every 50 ms, a healthy 16-batch run leaves
    one batch descriptor a batch in the ring and nothing else: no stall,
    no error, no dump file, no heartbeat still alive. A wall-clock
    ratio of watchdog on against off on the CPU rig flaps under
    parallel workers and says nothing about the chip."""
    from tpudl.frame import Frame

    frame = Frame({"x": np.arange(256 * 4, dtype=np.float32).reshape(256, 4)})
    obs_watchdog.start_watchdog(stall_s=30.0, interval=0.05)
    try:
        frame.map_batches(lambda b: b.sum(axis=1), ["x"], ["y"],
                          batch_size=16)
        time.sleep(0.2)  # a few scans of the finished run
    finally:
        obs_watchdog.stop_watchdog()
    snap = forensics.snapshot()
    assert sorted(b["index"] for b in snap["batches"]) == list(range(16))
    assert snap["stalls"] == [] and snap["errors"] == []
    assert not snap["heartbeats"]
    assert os.listdir(tmp_path) == []
