"""chip_smoke.py — the on-chip check's contract, as far as a CPU can
hold it: without the chip it must refuse (non-zero, naming the
platform, no result line), and ``--rehearse`` must walk all three
phases at toy size without ever printing ``ok: true``."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, devices=1):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # a placed cache: the run must not write into the checkout
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla_cache")
    return subprocess.run([sys.executable, SMOKE, *args], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=str(tmp_path))


def test_refuses_without_the_chip(tmp_path):
    r = _run([], tmp_path)
    assert r.returncode != 0
    assert "platform is 'cpu', not 'tpu'" in r.stderr
    assert "platform=cpu" in r.stdout          # says what it found
    assert '"ok"' not in r.stdout               # and reports no result
    assert "PASS" not in r.stdout


def test_rehearsal_walks_every_phase_and_never_says_ok(tmp_path):
    r = _run(["--rehearse"], tmp_path, devices=2)
    assert r.returncode == 0, r.stderr[-2000:]
    for phase in ("featurize", "train", "lm"):
        assert f"[{phase}] PASS" in r.stdout
    assert "ring_attention(use_pallas)" in r.stdout  # 2 devices: the ring
    result = json.loads(r.stdout.strip().splitlines()[-1])
    assert result["rehearsal"] is True
    assert "ok" not in result and '"ok": true' not in r.stdout
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": 2}
    assert set(result["phases"]) == {"featurize", "train", "lm"}
    cache = result["compile_cache"]
    assert cache["dir"] == str(tmp_path / "xla_cache")
    assert cache["entries_after"] > cache["entries_before"] == 0
