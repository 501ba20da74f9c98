"""The benchmark's own tests; they run on the CPU and are not part of tier-1.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from benchmark import common, flops, peaks, trace_reduce  # noqa: E402

MANIFEST = common.load_json(os.path.join(REPO, "BENCHMARK.json"))
CANDIDATES = [os.path.join(BENCH, "configs", f)
              for f in sorted(os.listdir(os.path.join(BENCH, "configs")))
              if f.endswith(".manifest.json")]


def with_candidates(manifest):
    """The manifest plus the entries of every ``configs/*.manifest.json``:
    cells that are built and rehearsed but not yet admitted."""
    out = json.loads(json.dumps(manifest))
    for path in CANDIDATES:
        extra = common.load_json(path)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            out[key] += extra[key]
    return out


FULL = with_candidates(MANIFEST)
CELLS = [w["name"] for w in FULL["workloads"]]
LISTED = [w["name"] for w in MANIFEST["workloads"]]


@pytest.fixture(scope="module")
def full_tree(tmp_path_factory):
    """A checkout in which the candidate cells are listed: this directory
    copied beside the merged manifest (the program comes by PYTHONPATH)."""
    root = tmp_path_factory.mktemp("full")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "BENCHMARK.json").write_text(json.dumps(FULL))
    return str(root)


# a traced window as a reader sees it: what trace_reduce.reduce returns
TRACE = {"window_s": 2.0, "busy_s": 1.5, "program_ms": 40.0,
         "program_runs": 50, "device_ops": [], "idle_gaps": []}
FACTS = {"images": 2048, "trace": TRACE, "device_kind": "TPU v5 lite",
         "devices": 1, "flops_per_run": 128 * 23.1e9,
         "setup": {"compile_s": 4.0},
         "pipeline": {"prepare_s": 18.0, "infeed_wait_s": 9.0,
                      "pass_wall_s": 10.0}}


def run_cell(*args, cwd=REPO):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=900)


# (a) every cell resolves; every reader runs on what the manifest names
@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_config_traffic_adapter_and_flops_block(cell):
    spec = common.resolve(FULL, cell)
    assert callable(common.load_adapter(spec.config["adapter"]).run)
    assert spec.traffic["kind"] in __import__(
        "benchmark.traffic", fromlist=["x"]).GENERATORS
    assert callable(spec.reference.forward)
    # the flops block is flops.per_example's own keyword arguments: a
    # mismatch here is what crashed the traced runs of the last attempt
    import inspect

    inspect.signature(flops.per_example).bind(
        spec.reference.forward, {}, **spec.config["flops"])
    inspect.signature(flops.per_example).bind(
        spec.reference.forward, {},
        **common.resolve(FULL, cell, rehearse=True).config["flops"])


@pytest.mark.parametrize("metric", [m["name"] for m in FULL["per_layer"]])
def test_every_per_layer_reader_reads_synthetic_facts(metric):
    read = common.load_reader(metric).read
    value = read(FACTS)
    assert isinstance(value, float) and value > 0
    # nothing to read -> nothing returned, and the harness leaves it out
    assert read({"trace": None}) is None


def test_reader_arithmetic():
    def read(name):
        return common.load_reader(name).read(FACTS)

    assert read("prepare_ms_per_image.featurize") == pytest.approx(
        18000 / 2048)
    assert read("infeed_wait_share.featurize") == pytest.approx(90.0)
    assert read("device_idle_share.train") == pytest.approx(25.0)
    assert read("program_ms.train") == 40.0
    # 128 x 23.1 GFLOP in 40 ms against 197 TFLOP/s
    assert read("program_mfu.train") == pytest.approx(
        100 * 128 * 23.1e9 / (0.040 * 197e12))
    with pytest.raises(KeyError):
        peaks.peak("TPU v99", "bf16_flops_per_s")


@pytest.mark.parametrize("manifest", [MANIFEST, FULL], ids=["listed", "full"])
def test_manifest_names_only_what_exists(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]].get("workloads", cells)
    for cell in cells:
        for section in ("end_to_end", "per_layer"):
            assert any(cell in m.get("workloads", cells) and m["name"] !=
                       "setup_s" for m in manifest[section]), (cell, section)
    assert {w["config"] for w in manifest["workloads"]} == {
        c["name"] for c in manifest["configs"]}
    for c in manifest["configs"]:
        data = common.load_json(os.path.join(REPO, c["file"]))
        assert data["reduced"] == c["reduced"]
        assert data["source"] == c["source"]


# (b) the reduction, on a hand-written event list
def test_trace_reduce_on_a_hand_written_event_list():
    ms = 1e6
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_step(1)", 0 * ms, 10 * ms),
                            ("jit_step(1)", 10 * ms, 12 * ms),
                            ("jit_eval(2)", 30 * ms, 4 * ms),   # after a gap
                            ("jit_step(1)", 34 * ms, 14 * ms),
                            ("jit_step(1)", 60 * ms, 40 * ms)],
            "XLA Ops": [("%fusion.1", 0, 6 * ms), ("%fusion.1", 10 * ms, 6 * ms),
                        ("%copy.2", 6 * ms, 1 * ms),
                        ("%late", 200 * ms, 5 * ms)]},     # outside the span
        "/device:TPU:1": {   # overlapping events must not count twice
            "XLA Modules": [("jit_step(1)", 0, 50 * ms),
                            ("jit_step(1)", 40 * ms, 20 * ms)]},
        "/host:CPU": {"XLA Modules": [("ignored", 0, 1)]},
    }
    planes = {k: v for k, v in planes.items()
              if trace_reduce.DEVICE_PLANE.match(k)}
    r = trace_reduce.reduce(planes, "jit_step")
    assert r["window_s"] == pytest.approx(0.100)
    # chip 0: 22 + 4 + 14 + 40 = 80 ms; chip 1: union(0-50, 40-60) = 60 ms
    assert r["busy_s_per_plane"] == pytest.approx([0.080, 0.060])
    assert r["busy_s"] == pytest.approx(0.070)
    assert r["program_runs"] == 4
    assert r["program_ms"] == pytest.approx(13.0)   # median(10, 12, 14, 40)
    assert r["idle_gaps"] == [["jit_step -> jit_step", pytest.approx(0.012)],
                              ["jit_step -> jit_eval", pytest.approx(0.008)]]
    assert r["idle_gap_total_s"] == pytest.approx(0.020)
    assert r["device_ops"] == [["%fusion.1", pytest.approx(0.012)],
                               ["%copy.2", pytest.approx(0.001)]]
    idle = common.load_reader("device_idle_share.train").read({"trace": r})
    assert idle == pytest.approx(30.0)
    # a host-clock window: nothing is cut, busy is set against its length
    r = trace_reduce.reduce(planes, "jit_step", window_ns=400 * ms)
    assert r["window_s"] == pytest.approx(0.4)
    assert r["busy_s_per_plane"] == pytest.approx([0.080, 0.060])
    assert [n for n, _ in r["device_ops"]] == ["%fusion.1", "%late", "%copy.2"]
    # ... and the waits before the first program and after the last are gaps
    assert r["idle_gaps"][0] == ["jit_step -> trace end", pytest.approx(0.3)]
    first = {"/device:TPU:0": {"XLA Modules": [("jit_fn(3)", 90 * ms, 5 * ms)]}}
    assert trace_reduce.reduce(first, "jit_fn", window_ns=100 * ms)[
        "idle_gaps"] == [["trace start -> jit_fn", pytest.approx(0.090)],
                         ["jit_fn -> trace end", pytest.approx(0.005)]]
    # no device plane (a CPU trace), or a program that never ran
    assert trace_reduce.reduce({}, "jit_step") is None
    assert trace_reduce.reduce(planes, "jit_other") is None
    assert trace_reduce.merged([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]


# (c) FLOPs from shapes
@pytest.mark.parametrize("config, model, gflop", [
    ("inceptionv3-featurize", "InceptionV3", 11.42),
    ("resnet50-sgd", "ResNet50", 7.72)])
def test_flops_from_shapes(config, model, gflop):
    from tpudl.zoo.registry import getKerasApplicationModel

    entry = next(c for c in FULL["configs"] if c["name"] == config)
    cfg = common.load_json(os.path.join(REPO, entry["file"]))
    ref = common.load_module(os.path.join(
        REPO, entry["file"][:-len(".json")] + ".py"), "ref_" + model)
    side = cfg["input_shape"][0]
    params = getKerasApplicationModel(model).init(0, image_size=(side, side))
    block = dict(cfg["flops"], passes=1)
    assert flops.per_example(ref.forward, params, **block) == pytest.approx(
        gflop * 1e9, rel=0.02)
    assert flops.per_example(ref.forward, params, **cfg["flops"]) == (
        pytest.approx(cfg["flops"]["passes"] * gflop * 1e9, rel=0.02))


def test_flops_of_a_dot_and_a_grouped_conv():
    import jax
    import jax.numpy as jnp

    a = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    b = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    assert flops.forward_flops(jnp.dot, a, b) == 2 * 4 * 8 * 16
    x = jax.ShapeDtypeStruct((1, 10, 10, 6), jnp.float32)
    k = jax.ShapeDtypeStruct((3, 3, 3, 12), jnp.float32)   # 2 groups of 3

    def conv(x, k):
        return jax.jit(lambda x, k: jax.lax.conv_general_dilated(
            x, k, (1, 1), "SAME", feature_group_count=2,
            dimension_numbers=("NHWC", "HWIO", "NHWC")))(x, k)  # nested jaxpr

    assert flops.forward_flops(conv, x, k) == 2 * (10 * 10 * 12) * (3 * 3 * 3)


# the plain references agree with the program in float32 on the CPU; on the
# chip the cells hold the program's bf16 results against them
@pytest.mark.parametrize("config, model, side", [
    ("inceptionv3-featurize", "InceptionV3", 139),
    ("resnet50-sgd", "ResNet50", 64)])
def test_plain_reference_agrees_with_the_zoo_in_float32(config, model, side):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudl.zoo.registry import getKerasApplicationModel

    ref = common.load_module(os.path.join(BENCH, "configs", config + ".py"),
                             "ref_" + model)
    m = getKerasApplicationModel(model)
    params = m.init(7, image_size=(side, side))
    x = np.random.default_rng(0).uniform(
        0, 255, (2, side, side, 3)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(params, jnp.asarray(x))
        if model == "InceptionV3":
            got = m.featurize(params, m.preprocess(jnp.asarray(x)))
        else:
            got = m.predict(params, (jnp.asarray(x) - 127.5) / 127.5)
    assert got.shape == want.shape
    assert common.rel_l2(got, want) < 1e-5


# (d) a new cell is new files and new entries only
def test_a_copied_traffic_file_and_config_resolve_with_no_other_edit(tmp_path):
    tree = tmp_path / "benchmark"
    shutil.copytree(BENCH, tree, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(tree / "traffic" / "jpeg-files.json",
                tree / "traffic" / "jpeg-files-again.json")
    for ext in (".json", ".py"):
        shutil.copy(tree / "configs" / ("inceptionv3-featurize" + ext),
                    tree / "configs" / ("inceptionv3-again" + ext))
    shutil.copy(tree / "readers" / "program_ms.py",
                tree / "readers" / "program_ms_again.py")
    manifest = json.loads(json.dumps(FULL))
    manifest["configs"].append({
        "name": "inceptionv3-again", "reduced": [], "source": "x", "why": "x",
        "file": "benchmark/configs/inceptionv3-again.json"})
    manifest["workloads"].append({
        "name": "again", "config": "inceptionv3-again",
        "traffic": "jpeg-files-again", "chips": 1, "why": "x"})
    spec = common.resolve(manifest, "again", root=str(tree))
    assert spec.config["model"] == "InceptionV3"
    assert spec.traffic["n_files"] == 2048
    assert spec.reference.__file__.endswith("inceptionv3-again.py")
    assert common.load_adapter(spec.config["adapter"], root=str(tree)).run
    assert common.load_reader("program_ms_again.serve", root=str(tree)).read(
        FACTS) == 40.0
    with pytest.raises(SystemExit):
        common.resolve(FULL, "again")


# (e) the rehearsal runs both cells, traced and not, and prints no verdict
@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_and_prints_no_result(cell, trace, full_tree):
    p = run_cell("--workload", cell, "--seed", "3000000019", "--seconds", "1",
                 "--trace", trace, "--rehearse",
                 cwd=REPO if cell in LISTED else full_tree)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("benchmark: facts ")
    facts = json.loads(last[len("benchmark: facts "):])
    assert facts["rehearsal"] is True and facts["correct"] is True
    assert facts["compiles_in_window"] == 0
    assert "COMPILED INSIDE THE WINDOW" not in p.stdout
    if trace == "1":
        assert "no device plane" in p.stdout and facts["trace"] is None
    assert not any(line.startswith('{"correct"')
                   for line in p.stdout.splitlines())


# (f) no accelerator, no result
def test_without_a_tpu_the_command_exits_nonzero_naming_the_platform():
    p = run_cell("--workload", LISTED[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0")
    assert p.returncode != 0
    assert "platform is 'cpu'" in p.stderr
    assert '"correct"' not in p.stdout


def test_alone_with_the_manifest_the_command_exits_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", LISTED[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env={**env, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and '"correct"' not in p.stdout
    assert "tpudl" in p.stderr
