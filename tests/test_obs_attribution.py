"""Attribution plane (ISSUE 20): scoped ledgers, carries, per-tenant top.

The acceptance matrix for OBSERVABILITY.md "Attribution plane": scope
semantics and the cross-pool carries (prepare pool, dispatch window,
serve client threads, HPO-style trial pools), the LRU-bounded
ScopeLedger and its reconciliation invariant (per-scope sums plus the
explicit ``unattributed`` bucket == the global counters, EXACTLY), THE
two-tenant serve+fit acceptance behind a schema-valid status file, the
v3 flight-dump ledger + doctor evidence + the offline ``python -m
tpudl.obs ledger`` CLI, the validator-family contracts (including the
labeled-series cardinality guard), a TSAN-armed pass over the new
``obs.attribution.ledger`` lock, and the <5% scoped-vs-unscoped
overhead guard (the PR-3/PR-18 discipline: interleaved arms, medians,
absolute slack).
"""

import importlib.util
import json
import os
import statistics
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from tpudl import obs
from tpudl.frame import Frame
from tpudl.obs import attribution as attr
from tpudl.obs import doctor as obs_doctor
from tpudl.obs import flight
from tpudl.obs import live
from tpudl.obs import watchdog as obs_watchdog
from tpudl.testing import tsan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


def _load_tool(name):
    """Import a tools/ validator by path (the house pattern). tools/
    goes on sys.path first so validate_status's ``from validate_dump
    import validate_ledger_section`` resolves to the real section
    checks, not the ImportError fallback."""
    if TOOLS not in sys.path:
        sys.path.insert(0, TOOLS)
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(TOOLS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _metric(name):
    entry = obs.snapshot().get(name)
    return entry["value"] if entry else 0.0


@pytest.fixture(autouse=True)
def clean_attr():
    """Fresh ledger + registry per test: the reconciliation invariant
    is asserted from zero, so residue from other modules' tests (which
    share both process-global singletons) must not leak in."""
    obs.get_registry().reset()
    attr.reset_ledger()
    yield
    obs.get_registry().reset()
    attr.reset_ledger()


# ---------------------------------------------------------------------------
# scope semantics + carry
# ---------------------------------------------------------------------------

class TestScope:
    def test_key_format(self):
        assert attr.Scope(tenant="a").key == "tenant=a"
        assert attr.Scope(tenant="a", job="j", run="r").key == \
            "tenant=a|job=j|run=r"
        assert attr.Scope(job="j", run="r").key == "job=j|run=r"
        assert attr.Scope().key is None

    def test_immutable(self):
        sc = attr.Scope(tenant="a")
        with pytest.raises(AttributeError):
            sc.tenant = "b"

    def test_jobspec_attributes_by_fingerprint(self, tmp_path):
        from tpudl.jobs.spec import JobSpec

        spec = JobSpec("fit", str(tmp_path))
        sc = attr.Scope(job=spec)
        assert sc.job == spec.fingerprint()[:12]
        assert sc.key == f"job={spec.fingerprint()[:12]}"

    def test_nested_scopes_merge(self):
        assert attr.current_scope() is None
        with obs.scope(tenant="t"):
            with obs.scope(run="r"):
                assert attr.current_scope().key == "tenant=t|run=r"
            assert attr.current_scope().key == "tenant=t"
            with obs.scope(tenant="t2", job="j"):
                assert attr.current_scope().key == "tenant=t2|job=j"
        assert attr.current_scope() is None

    def test_carry_captures_at_wrap_time(self):
        """The submit-site contract: the scope bound is the one active
        when carry() ran, not when the worker executes."""
        def work():
            attr.charge("rows_in", 1)

        with obs.scope(tenant="capture"):
            bound = attr.carry(work)
        th = threading.Thread(target=bound)  # no scope on this thread
        th.start()
        th.join()
        snap = attr.ledger_snapshot()
        assert snap["scopes"]["tenant=capture"]["rows_in"] == 1
        assert snap["unattributed"]["rows_in"] == 0

    def test_carry_without_scope_is_identity(self):
        def work():
            pass

        assert attr.carry(work) is work


# ---------------------------------------------------------------------------
# the ledger: charges, credits, LRU eviction, reconciliation
# ---------------------------------------------------------------------------

class TestLedger:
    def test_charge_routes_by_scope(self):
        attr.charge("rows_in", 5)  # no scope → unattributed
        with obs.scope(tenant="a"):
            attr.charge("rows_in", 3)
        snap = attr.ledger_snapshot()
        assert snap["unattributed"]["rows_in"] == 5
        assert snap["scopes"]["tenant=a"]["rows_in"] == 3
        assert attr.ledger_totals()["rows_in"] == 8

    def test_unknown_field_raises(self):
        with pytest.raises(KeyError, match="unknown ledger field"):
            attr.charge("nope", 1)

    def test_create_false_credits_unattributed(self):
        """A credit against an absent (evicted/folded) key lands where
        its debits went — the HBM credit path."""
        key = attr.charge("hbm_bytes", -64, key="tenant=gone",
                          create=False)
        assert key is None
        snap = attr.ledger_snapshot()
        assert "tenant=gone" not in snap["scopes"]
        assert snap["unattributed"]["hbm_bytes"] == -64

    def test_hbm_peak_is_high_water(self):
        with obs.scope(tenant="h"):
            attr.charge("hbm_bytes", 100)
            attr.charge("hbm_bytes", -40)
            attr.charge("hbm_bytes", 10)
        row = attr.ledger_snapshot()["scopes"]["tenant=h"]
        assert row["hbm_bytes"] == 70
        assert row["hbm_peak_bytes"] == 100

    def test_lru_eviction_folds_into_unattributed(self, monkeypatch):
        monkeypatch.setenv("TPUDL_OBS_SCOPES", "2")
        attr.reset_ledger()
        for name, n in (("a", 10), ("b", 20), ("c", 30)):
            with obs.scope(tenant=name):
                attr.charge("rows_in", n)
        snap = attr.ledger_snapshot()
        assert set(snap["scopes"]) == {"tenant=b", "tenant=c"}
        assert snap["evicted"] == 1
        assert snap["unattributed"]["rows_in"] == 10  # a's fold
        assert _metric("attribution.scopes_evicted") == 1
        # conservation: eviction never loses rows
        assert attr.ledger_totals()["rows_in"] == 60

    def test_lru_recency_protects_touched_scopes(self, monkeypatch):
        monkeypatch.setenv("TPUDL_OBS_SCOPES", "2")
        attr.reset_ledger()
        attr.charge("rows_in", 1, key="tenant=a")
        attr.charge("rows_in", 1, key="tenant=b")
        attr.charge("rows_in", 1, key="tenant=a")  # a is now newest
        attr.charge("rows_in", 1, key="tenant=c")  # evicts b, not a
        snap = attr.ledger_snapshot()
        assert set(snap["scopes"]) == {"tenant=a", "tenant=c"}

    def test_reconcile_clean_and_mismatch(self):
        with obs.scope(tenant="w"):
            attr.charge("wire_bytes", 128)
        obs.counter("data.wire.bytes_shipped").inc(128)
        rec = attr.reconcile()
        assert rec["ok"], rec
        # now break the invariant: a global inc with no paired charge
        obs.counter("serve.completed").inc()
        rec = attr.reconcile()
        assert not rec["ok"]
        bad = [c for c in rec["checks"] if not c["ok"]]
        assert [c["field"] for c in bad] == ["serve_completed"]
        assert bad[0]["global"] == 1 and bad[0]["ledger"] == 0

    def test_totals_of_excludes_peak_from_sum(self):
        snap = {"scopes": {"tenant=a": {"hbm_peak_bytes": 100,
                                        "hbm_bytes": 10}},
                "unattributed": {"hbm_peak_bytes": 50, "hbm_bytes": 1}}
        tot = attr.totals_of(snap)
        assert tot["hbm_bytes"] == 11
        assert tot["hbm_peak_bytes"] == 50  # unattributed only: a
        # high-water mark is not conserved, so scopes don't sum into it


# ---------------------------------------------------------------------------
# propagation: the executor pools, trial pools and serve client threads
# ---------------------------------------------------------------------------

def _run_frame(n):
    f = Frame({"x": np.arange(n, dtype=np.float32)})
    f.map_batches(lambda x: x * 2, ["x"], ["y"], batch_size=16)


class TestPropagation:
    def test_map_batches_charges_submitting_scope(self):
        """rows_in is charged on prepare-pool threads, rows_out on the
        dispatch/consumer side — both must land in the caller's scope
        via the _PipelineInfeed/_DispatchWindow carries."""
        with obs.scope(tenant="etl"):
            _run_frame(64)
        snap = attr.ledger_snapshot()
        row = snap["scopes"]["tenant=etl"]
        assert row["rows_in"] == 64
        assert row["rows_out"] == 64
        assert row["dispatch_s"] > 0
        assert snap["unattributed"]["rows_in"] == 0
        assert snap["unattributed"]["rows_out"] == 0

    def test_interleaved_runs_do_not_leak(self):
        """Two executors in flight at once under distinct tenants: each
        scope's row counts are exactly its own frame's — a carry that
        captured the wrong context would cross-charge."""
        def run(tenant, n):
            with obs.scope(tenant=tenant):
                _run_frame(n)

        threads = [threading.Thread(target=run, args=("ta", 48)),
                   threading.Thread(target=run, args=("tb", 80))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        scopes = attr.ledger_snapshot()["scopes"]
        assert scopes["tenant=ta"]["rows_in"] == 48
        assert scopes["tenant=ta"]["rows_out"] == 48
        assert scopes["tenant=tb"]["rows_in"] == 80
        assert scopes["tenant=tb"]["rows_out"] == 80

    def test_trial_pool_carry_interleaved(self):
        """The HPO-pool shape: N submitters share one worker pool, each
        wrapping its submission with carry() — worker-thread charges
        follow the submitter, with no leakage across interleaving."""
        pool = ThreadPoolExecutor(max_workers=4)
        try:
            def submit_all(tenant, amounts):
                with obs.scope(tenant=tenant):
                    return [pool.submit(
                        attr.carry(lambda a=a: attr.charge("rows_in", a)))
                        for a in amounts]

            futs = submit_all("hpo-a", [1] * 20) + \
                submit_all("hpo-b", [2] * 20)
            for f in futs:
                f.result(timeout=30)
        finally:
            pool.shutdown()
        scopes = attr.ledger_snapshot()["scopes"]
        assert scopes["tenant=hpo-a"]["rows_in"] == 20
        assert scopes["tenant=hpo-b"]["rows_in"] == 40

    def test_serve_request_captures_client_scope(self):
        from tpudl.serve import ServeRequest

        with obs.scope(tenant="client"):
            req = ServeRequest(np.array([1, 2, 3], np.int32), 4)
        assert req.scope.key == "tenant=client"
        assert ServeRequest(np.array([1], np.int32), 2).scope is None

    def test_loadgen_tenant_stamping(self):
        """Two tenants under the load generator: ``tenant=("a", "b")``
        alternates client scopes, so the closed loop produces exactly
        two ledger rows whose completions sum to the request count."""
        from tpudl.serve import ModelRegistry, Server, run_closed_loop
        from tpudl.zoo.transformer import TinyCausalLM

        lm = TinyCausalLM(vocab=64, dim=32, heads=4, layers=2,
                          max_len=64)
        reg = ModelRegistry()
        reg.add_model("default", lm, lm.init(0), slots=2, cache_len=32,
                      warm=False)
        rng = np.random.default_rng(2)

        def make_prompt(i):
            return rng.integers(1, 64, size=3 + i % 4).astype(np.int32)

        srv = Server(reg).start_async()
        try:
            load = run_closed_loop(srv, make_prompt, requests=8,
                                   clients=2, max_new=3,
                                   tenant=("a", "b"))
        finally:
            srv.close()
        scopes = attr.ledger_snapshot()["scopes"]
        assert set(scopes) == {"tenant=a", "tenant=b"}
        done = sum(row["serve_completed"] for row in scopes.values())
        assert done == load["completed"] == 8
        assert attr.reconcile()["ok"]


# ---------------------------------------------------------------------------
# status file + obs top surfaces
# ---------------------------------------------------------------------------

@pytest.fixture()
def status_env(monkeypatch, tmp_path):
    live.stop_status_writer()
    obs_watchdog.get_registry().clear()
    monkeypatch.setenv("TPUDL_STATUS_DIR", str(tmp_path))
    yield tmp_path
    live.stop_status_writer()
    obs_watchdog.get_registry().clear()


class TestStatusAndTop:
    def test_status_section_rates_and_share(self):
        assert attr.status_section() is None  # no charges yet
        with obs.scope(tenant="r"):
            attr.charge("rows_in", 10)
            attr.charge("hbm_bytes", 100)
        first = attr.status_section()
        row = first["scopes"]["tenant=r"]
        assert row["rows_s"] is None  # no previous tick
        assert row["hbm_share"] == 1.0
        time.sleep(0.02)
        with obs.scope(tenant="r"):
            attr.charge("rows_in", 10)
        second = attr.status_section()
        assert second["scopes"]["tenant=r"]["rows_s"] > 0

    def test_status_file_schema_valid_and_rendered(self, status_env):
        with obs.scope(tenant="hud"):
            attr.charge("rows_in", 7)
            attr.charge("tokens_out", 11)
        path = live.write_status(str(status_env))
        assert path is not None
        vs = _load_tool("validate_status")
        assert vs.validate_status(path) == []
        payload = json.loads(open(path).read())
        assert payload["ledger"]["scopes"]["tenant=hud"]["rows_in"] == 7
        text = live.render(live.read_statuses(str(status_env)))
        assert "tenants:" in text
        assert "tenant=hud" in text

    def test_fleet_merge_across_processes(self, status_env):
        """Two processes' ledgers merge into per-tenant fleet rows:
        shared tenants sum, hbm_share is recomputed over the merged
        resident total."""
        with obs.scope(tenant="shared"):
            attr.charge("rows_in", 5)
            attr.charge("hbm_bytes", 100)
        live.write_status(str(status_env))
        (st,) = live.read_statuses(str(status_env))
        st2 = json.loads(json.dumps(st))
        st2["pid"] = st["pid"] + 1
        st2["ledger"]["scopes"]["tenant=other"] = dict(
            st2["ledger"]["scopes"]["tenant=shared"])
        text = live.render([st, st2])
        assert "fleet tenants (2 procs" in text
        assert "tenant=shared" in text
        assert "tenant=other" in text


# ---------------------------------------------------------------------------
# v3 flight dumps, doctor evidence, the offline CLI
# ---------------------------------------------------------------------------

@pytest.fixture()
def forensics(monkeypatch, tmp_path):
    monkeypatch.setenv("TPUDL_FLIGHT_DIR", str(tmp_path))
    rec = flight.get_recorder()
    rec.reset()
    yield tmp_path
    rec.reset()


def _charge_paired(tenant="big", rows=100, wire=256):
    """Charges WITH their paired global increments, so the embedded
    reconciliation verdict is clean by construction."""
    with obs.scope(tenant=tenant):
        attr.charge("rows_in", rows)
        attr.charge("wire_bytes", wire)
    obs.counter("data.wire.bytes_shipped").inc(wire)


class TestDumpDoctorCli:
    def test_dump_v3_carries_reconciled_ledger(self, forensics):
        _charge_paired()
        path = obs.dump(reason="manual")
        vd = _load_tool("validate_dump")
        assert vd.validate_dump(path) == []
        (payload,) = obs_doctor.load_dumps(str(forensics))
        assert payload["version"] >= 3
        led = payload["ledger"]
        assert led["scopes"]["tenant=big"]["wire_bytes"] == 256
        assert led["reconcile"]["ok"] is True

    def test_doctor_names_dominant_scope(self, forensics):
        _charge_paired(tenant="big", rows=100)
        _charge_paired(tenant="small", rows=5)
        obs.dump(reason="manual")
        merged = obs_doctor.merge_dumps(
            obs_doctor.load_dumps(str(forensics)))
        diagnosis = obs_doctor.classify(merged)
        ev = [e for e in diagnosis["evidence"]
              if "dominant scope at death" in e]
        assert ev and "tenant=big" in ev[0]

    def test_doctor_flags_broken_reconciliation(self, forensics):
        with obs.scope(tenant="x"):
            attr.charge("serve_completed", 3)  # no paired global inc
        obs.dump(reason="manual")
        merged = obs_doctor.merge_dumps(
            obs_doctor.load_dumps(str(forensics)))
        diagnosis = obs_doctor.classify(merged)
        assert any("ledger reconciliation BROKEN" in e
                   for e in diagnosis["evidence"])

    def test_cli_ledger_rc_contract(self, forensics, tmp_path):
        """rc 0 = every artifact reconciles, 1 = mismatch somewhere,
        2 = nothing ledger-bearing under the path."""
        _charge_paired()
        obs.dump(reason="manual")
        env = dict(os.environ, JAX_PLATFORMS="cpu")

        def run(path):
            return subprocess.run(
                [sys.executable, "-m", "tpudl.obs", "ledger", path],
                capture_output=True, text=True, env=env, cwd=REPO,
                timeout=120)

        good = run(str(forensics))
        assert good.returncode == 0, good.stderr
        assert "RECONCILED" in good.stdout
        assert "tenant=big" in good.stdout

        empty = tmp_path / "empty"
        empty.mkdir()
        assert run(str(empty)).returncode == 2

        bad_dir = tmp_path / "bad"
        bad_dir.mkdir()
        row = {f: 0.0 for f in attr.LEDGER_FIELDS}
        row["wire_bytes"] = 999.0  # no matching global counter
        (bad_dir / "tpudl-status-1.json").write_text(json.dumps({
            "pid": 1, "ts": 1.0,
            "ledger": {"scopes": {"tenant=liar": row},
                       "unattributed": {f: 0.0
                                        for f in attr.LEDGER_FIELDS},
                       "evicted": 0, "cap": 64},
            "metrics": {}}))
        bad = run(str(bad_dir))
        assert bad.returncode == 1
        assert "MISMATCH" in bad.stdout


# ---------------------------------------------------------------------------
# validator-family contracts
# ---------------------------------------------------------------------------

def _ledger_fixture():
    zero = {f: 0.0 for f in attr.LEDGER_FIELDS}
    return {"scopes": {"tenant=a": dict(zero)},
            "unattributed": dict(zero), "evicted": 0, "cap": 64}


class TestValidators:
    def test_ledger_section_accepts_good_and_none(self):
        vd = _load_tool("validate_dump")
        assert vd.validate_ledger_section(_ledger_fixture()) == []
        assert vd.validate_ledger_section(None) == []

    def test_ledger_section_rejects_malformed(self):
        vd = _load_tool("validate_dump")
        led = _ledger_fixture()
        del led["scopes"]["tenant=a"]["wire_bytes"]
        assert any("wire_bytes" in e
                   for e in vd.validate_ledger_section(led))
        led = _ledger_fixture()
        led["scopes"]["tenant=a"]["hbm_share"] = 1.5
        assert any("hbm_share" in e
                   for e in vd.validate_ledger_section(led))
        led = _ledger_fixture()
        led["evicted"] = -1
        assert vd.validate_ledger_section(led)
        assert any("not an object" in e
                   for e in vd.validate_ledger_section("nope"))

    def test_dump_v3_requires_ledger_key(self, forensics):
        _charge_paired()
        path = obs.dump(reason="manual")
        vd = _load_tool("validate_dump")
        import gzip

        payload = json.loads(gzip.open(path, "rt").read())
        assert vd.validate_payload(payload) == []
        del payload["ledger"]
        assert any("ledger" in e
                   for e in vd.validate_payload(payload))

    def test_two_tenant_ledger_with_reconcile_satisfies_section_schema(
            self):
        """A two-tenant ledger carrying its reconciliation verdict (the
        block a closed-loop serve run under ``tenant=("a", "b")``
        leaves) satisfies the shared section schema."""
        vd = _load_tool("validate_dump")
        led = _ledger_fixture()
        led["scopes"]["tenant=b"] = dict(led["unattributed"])
        led["reconcile"] = {"ok": True, "checks": []}
        assert vd.validate_ledger_section(led) == []

    def test_metrics_cardinality_breach_is_rc2(self, tmp_path, capsys):
        """Minting per-label names into one family breaches the
        labeled-series bound and outranks schema errors (rc 2)."""
        vm = _load_tool("validate_metrics")
        entries = {f"fam.sub.s{i}": {"type": "counter", "value": 1}
                   for i in range(vm.SERIES_BOUND + 4)}
        p = tmp_path / "sink.jsonl"
        p.write_text(json.dumps({"ts": 1.0, "event": "snapshot",
                                 "pid": 1, "metrics": entries}) + "\n")
        assert vm.main(["validate_metrics.py", str(p)]) == 2
        out = capsys.readouterr()
        assert "attribution ledger" in out.err
        # a raised bound clears it — the guard is the knob, not the data
        assert vm.main(["validate_metrics.py", "--series-bound", "1000",
                        str(p)]) == 0


# ---------------------------------------------------------------------------
# TSAN-armed pass + the overhead guard
# ---------------------------------------------------------------------------

@pytest.fixture()
def armed():
    """Arm the sanitizer, then rebuild the ledger so its lock is an
    instrumented TsanLock (arming only affects locks created after)."""
    prev = tsan.ENABLED
    tsan.reset()
    tsan.arm()
    attr.reset_ledger()
    yield
    tsan.ENABLED = prev
    tsan.reset()
    attr.reset_ledger()


class TestConcurrencyAndOverhead:
    def test_armed_concurrent_charges_clean_and_exact(self, armed):
        """8 threads hammer 4 scopes through the instrumented ledger
        lock while a reader snapshots: no sanitizer findings, and the
        totals are EXACT (charges are never lost or double-counted
        under contention)."""
        stop = threading.Event()

        def reader():
            while not stop.is_set():
                attr.ledger_snapshot()
                attr.ledger_totals()

        def writer(i):
            with obs.scope(tenant=f"t{i % 4}"):
                for _ in range(200):
                    attr.charge("rows_in", 1)

        rd = threading.Thread(target=reader)
        rd.start()
        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        rd.join()
        assert attr.ledger_totals()["rows_in"] == 8 * 200
        bad = [f for f in tsan.findings()
               if "obs.attribution.ledger" in str(f)]
        assert bad == [], bad

    def test_scoped_overhead_under_5pct(self):
        """Attribution costs < 5% on a real executor run: the same
        workload inside vs outside a scope (interleaved arms + medians
        + absolute slack, the PR-3/PR-18 discipline)."""
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
        scoped, plain = [], []
        for t in range(5):
            for arm in (("scoped", "plain") if t % 2 == 0
                        else ("plain", "scoped")):
                if arm == "scoped":
                    with obs.scope(tenant="bench", run=f"r{t}"):
                        scoped.append(run_once())
                else:
                    plain.append(run_once())
        med_scoped = statistics.median(scoped)
        med_plain = statistics.median(plain)
        assert med_scoped <= med_plain * 1.05 + 0.010, (
            f"attribution too slow: {med_scoped:.4f}s vs "
            f"{med_plain:.4f}s (trials {scoped} vs {plain})")


# ---------------------------------------------------------------------------
# THE two-tenant acceptance
# ---------------------------------------------------------------------------

def _toy():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    Xall = rng.normal(size=(512, 4)).astype(np.float32)
    w_true = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
    yall = Xall @ w_true + 0.1

    def data_fn(step, batch=32):
        i = (step * batch) % (len(Xall) - batch + 1)
        return Xall[i:i + batch], yall[i:i + batch]

    def loss_fn(params, x, y):
        pred = x @ params["w"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    params = {"w": jnp.zeros((4, 1)), "b": jnp.zeros(())}
    return data_fn, loss_fn, params


class TestTwoTenantAcceptance:
    def test_serve_plus_fit_two_rows_exact_reconcile(self, status_env):
        """ISSUE 20 acceptance: a serve loop and a concurrent
        Trainer.fit tagged as distinct tenants in ONE process produce
        two live rows in ``obs top`` backed by a schema-valid status
        file, and the ledger reconciles EXACTLY against the global
        serve counters."""
        import optax

        from tpudl.serve import ModelRegistry, RequestQueue, Server
        from tpudl.train import Trainer
        from tpudl.zoo.transformer import TinyCausalLM

        lm = TinyCausalLM(vocab=64, dim=32, heads=4, layers=2,
                          max_len=64)
        params = lm.init(0)
        reg = ModelRegistry()
        reg.add_model("default", lm, params, slots=2, cache_len=32,
                      warm=False)
        srv = Server(reg, RequestQueue(cap=16)).start_async()
        steps, batch = 12, 32
        train_err = []

        def train():
            try:
                data_fn, loss_fn, p0 = _toy()
                with obs.scope(tenant="train-b"):
                    Trainer(loss_fn, optax.sgd(0.1)).fit(
                        p0, data_fn, steps=steps)
            except Exception as e:  # surfaced below — a daemonless
                train_err.append(e)  # thread must not swallow failure

        th = threading.Thread(target=train)
        th.start()
        rng = np.random.default_rng(1)
        plens = (3, 5, 7, 9)
        try:
            with obs.scope(tenant="serve-a"):
                reqs = [srv.submit(
                    rng.integers(1, 64, size=n).astype(np.int32), 4)
                    for n in plens]
            outs = [r.result(timeout=120) for r in reqs]
            th.join(timeout=120)
        finally:
            srv.close()
        assert not train_err, train_err
        assert not th.is_alive()

        scopes = attr.ledger_snapshot()["scopes"]
        serve_row = scopes["tenant=serve-a"]
        train_row = scopes["tenant=train-b"]
        assert serve_row["serve_completed"] == len(reqs)
        assert serve_row["slo_samples"] == len(reqs)
        assert serve_row["tokens_in"] == sum(plens)
        assert serve_row["tokens_out"] == sum(o.size for o in outs)
        assert train_row["rows_in"] == steps * batch

        # the invariant, exactly: per-scope sums + unattributed ==
        # the global counters the serve loop published
        rec = attr.reconcile()
        assert rec["ok"], rec
        by_field = {c["field"]: c for c in rec["checks"]}
        assert by_field["serve_completed"]["global"] == len(reqs)
        assert by_field["slo_samples"]["global"] == len(reqs)

        path = live.write_status(str(status_env))
        vs = _load_tool("validate_status")
        assert vs.validate_status(path) == []
        text = live.render(live.read_statuses(str(status_env)))
        assert "tenant=serve-a" in text
        assert "tenant=train-b" in text
        assert "tenants:" in text
