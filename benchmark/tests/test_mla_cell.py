"""What the cell ``joyai-llm-flash-ep32-train-8k`` adds to the benchmark:
the count of needed work of a latent-attention stack with a
multi-token-prediction module, the configuration with its cut, the adapter
that renames ``lm_train``'s parts, and the control. That the cell resolves
and rehearses is tested where every cell's is too, in ``test_benchmark.py``,
unedited: it reads ``BENCHMARK.json``."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common, flops_lm, flops_mla  # noqa: E402

CELL = "joyai-llm-flash-ep32-train-8k"
MANIFEST = common.load_json(os.path.join(REPO, "BENCHMARK.json"))
CONFIG = common.load_json(os.path.join(
    REPO, "benchmark", "configs", "joyai-llm-flash-ep32.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# ---- needed work ----------------------------------------------------------
def test_step_flops_at_the_published_widths():
    """ISSUE 34's arithmetic: of ~110 needed TFLOP a step of 32,768
    tokens, six latent-attention blocks' causal products 49 and their
    projections 31, both head passes 13, the dense layer 9, five shared
    experts 5, the held pairs 1 (an even router: 0.25 a token and routed
    layer)."""
    parts = flops_mla.forward_parts(CONFIG, 8192)
    step = {k: 3 * 32768 * v / 1e12 for k, v in parts.items()}
    assert step["attention_causal"] == pytest.approx(49.5, abs=0.1)
    assert step["mla_proj"] == pytest.approx(31.1, abs=0.1)
    assert step["head"] == pytest.approx(13.0, abs=0.1)
    assert step["dense_ff"] == pytest.approx(8.66, abs=0.05)
    assert step["shared_ff"] == pytest.approx(4.64, abs=0.05)
    assert step["mtp_merge"] == pytest.approx(1.65, abs=0.01)
    assert step["router"] == pytest.approx(0.52, abs=0.01)
    # one block: 26.35 M multiply-adds of projections a token
    assert flops_mla.mla_parts(CONFIG, 8192)["proj"] == 2 * (
        2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
        + 32 * 128 * 2048)
    assert flops_mla.routed_layers(CONFIG) == 5
    pairs = round(5 * 0.25 * 32768)
    assert flops_mla.experts_work(CONFIG, pairs)["flops"] == pytest.approx(
        1.16e12, rel=5e-3)
    assert flops_mla.step_flops(CONFIG, 32768, 8192, pairs) == pytest.approx(
        110.2e12, rel=2e-3)
    assert flops_mla.attention_flops(CONFIG, 32768, 8192) == pytest.approx(
        49.5e12, rel=2e-3)
    # the module: M, one block, a shared expert, a router, a head pass
    assert flops_mla.mtp_flops(CONFIG, 32768, 8192) == pytest.approx(
        1.65e12 + (49.5e12 + 31.1e12) / 6 + 4.64e12 / 5 + 0.52e12 / 5
        + 13.0e12 / 2, rel=2e-3)


def test_flops_against_a_hand_count_at_toy_widths():
    cfg = {"hidden_size": 8, "num_hidden_layers": 3,
           "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
           "num_attention_heads": 2, "q_lora_rank": 6, "kv_lora_rank": 4,
           "qk_nope_head_dim": 4, "qk_rope_head_dim": 2, "v_head_dim": 3,
           "intermediate_size": 10, "moe_intermediate_size": 6,
           "n_shared_experts": 1, "n_routed_experts": 2, "vocab_size": 32,
           "published": {"n_routed_experts": 16}}
    mla = flops_mla.mla_parts(cfg, 6)
    # W_qa 8x6, W_qb 6x(2x6), W_kva 8x(4+2), W_kvb 4x(2x7), W_o (2x3)x8
    assert mla["proj"] == 2 * (48 + 72 + 48 + 56 + 48)
    # a head: scores over 4 + 2, values over 3; (6 + 1) / 2 keys a query
    assert mla["causal"] == 2 * 2 * (6 + 3) * 3.5
    parts = flops_mla.forward_parts(cfg, 6)
    assert parts == {
        "mla_proj": 4 * mla["proj"],            # 3 layers and the module
        "attention_causal": 4 * mla["causal"],
        "dense_ff": 3 * 2 * 8 * 10,
        "shared_ff": 3 * (3 * 2 * 8 * 6),       # 2 routed layers + module
        "router": 3 * 2 * 8 * 16,
        "mtp_merge": 2 * 16 * 8,
        "head": 2 * (2 * 8 * 32)}
    assert flops_mla.pair_flops(cfg) == 3 * 2 * 8 * 6
    assert flops_mla.routed_layers(cfg) == 3
    assert flops_mla.step_flops(cfg, 12, 6, 5) == 3 * (
        12 * sum(parts.values()) + 5 * 288)
    work = flops_mla.experts_work(cfg, 5)
    assert work["flops"] == 3 * 5 * 288
    # 3 products x 3 passes x (rows x (in + out) + the held experts' weights)
    assert work["bytes"] == 2 * 9 * (5 * (8 + 6) + 3 * 2 * 8 * 6)
    assert flops_mla.attention_flops(cfg, 12, 6) == 3 * 12 * 4 * mla["causal"]
    assert flops_mla.mtp_flops(cfg, 12, 6) == 3 * 12 * (
        2 * 16 * 8 + mla["proj"] + mla["causal"] + 3 * 2 * 8 * 6
        + 2 * 8 * 16 + 2 * 8 * 32)
    assert flops_mla.mtp_flops({**cfg, "num_nextn_predict_layers": 0},
                               12, 6) == 0


def test_the_counts_share_their_signatures():
    """``lm_train``'s ``Cell`` calls either module through the same
    functions; this count follows the pairs too."""
    import inspect

    for fn in ("step_flops", "experts_work", "forward_flops_per_token"):
        assert (inspect.signature(getattr(flops_mla, fn))
                == inspect.signature(getattr(flops_lm, fn))), fn
    few = flops_mla.experts_work(CONFIG, 1000)
    many = flops_mla.experts_work(CONFIG, 40960)
    assert many["flops"] == pytest.approx(40.96 * few["flops"])
    assert flops_mla.step_flops(CONFIG, 32768, 8192, 0) < (
        flops_mla.step_flops(CONFIG, 32768, 8192, 40960))
    # the causal products are the larger part of a block at 8,192 tokens
    mla = flops_mla.mla_parts(CONFIG, 8192)
    assert 0.6 < mla["causal"] / (mla["causal"] + mla["proj"]) < 0.65


# ---- the configuration file ----------------------------------------------
def test_configuration_carries_the_cut_beside_the_published_counts():
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    published = CONFIG["published"]
    assert (CONFIG["num_hidden_layers"], published["num_hidden_layers"]) == (
        5, 40)
    assert published["n_routed_experts"] == 256 == 32 * CONFIG[
        "n_routed_experts"]
    assert published["vocab_size"] == 129280 == 8 * CONFIG["vocab_size"]
    assert CONFIG["experts_held"] == [0, CONFIG["n_routed_experts"]]
    assert CONFIG["vocab_slice"] == [0, CONFIG["vocab_size"]]
    # no width is cut
    widths = {"hidden_size": 2048, "num_attention_heads": 32,
              "num_key_value_heads": 32, "q_lora_rank": 1536,
              "kv_lora_rank": 512, "qk_head_dim": 192,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128, "head_dim": 64, "intermediate_size": 7168,
              "moe_intermediate_size": 768, "num_experts_per_tok": 8,
              "n_shared_experts": 1, "routed_scaling_factor": 2.5,
              "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
              "rope_theta": 32000000, "rope_interleave": True,
              "rope_scaling": None, "tie_word_embeddings": False,
              "model_type": "joyai_llm_flash"}
    for key, value in widths.items():
        assert CONFIG[key] == value, key
    assert CONFIG["norm_eps"] == CONFIG["rms_norm_eps"] == 1e-6
    assert CONFIG["mtp_weight"] == 0.3
    for key in ("mtp_weight", "mtp_concatenation_order",
                "mtp_stream_before_the_final_norm", "optimizer",
                "initialisation", "no_document_mask", "no_balancing_loss",
                "lone_share"):
        assert key in CONFIG["assumed"], key
    # what of the published model is left out is said, not guessed
    for word in ("absorbed decode", "cache", "YaRN", "group-limited",
                 "depth > 1"):
        assert word in CONFIG["not_built"], word
    assert "32 chips" in CONFIG["deployment"]
    assert "8,192" in CONFIG["deployment"] and "1,024" in CONFIG["deployment"]
    assert "an eighth" in CONFIG["deployment"]
    assert CONFIG["optimizer"] == {"name": "adamw", "learning_rate": 3e-4,
                                   "b1": 0.9, "b2": 0.95,
                                   "weight_decay": 0.1}
    limits = CONFIG["check"]
    assert 0.9 <= limits["route_agreement_min"] < 1
    assert limits["update_rel_l2"] < 2e-3    # under what a lost decay reads
    assert set(limits["grad_rel_l2"]) == {"mla", "experts", "routers",
                                          "shared_ff", "dense_ff", "mtp",
                                          "table", "head", "norms"}
    assert all(0 < v <= 0.06 for v in limits["grad_rel_l2"].values())
    # ~15 steps a window: the dropped intervals leave most of them
    assert CONFIG["drop_intervals"] + 2 <= 10
    assert "PLACEHOLDER" not in json.dumps(CONFIG)


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the catalog beside the model-configs guide")
def test_every_number_of_the_catalogs_config_is_in_the_file():
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if "JoyAI-LLM-Flash" in line)
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] != value and CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key


def test_manifest_lists_the_cell_under_the_nine_metrics_named():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert entry == {**entry, "config": "joyai-llm-flash-ep32",
                     "traffic": "packed-tokens-8k", "chips": 1}
    # appended after the cells that were there (a later cell comes after
    # this one: "last" would hold for one PR only)
    cells = [w["name"] for w in MANIFEST["workloads"]]
    assert cells.index(CELL) == 1 + cells.index(
        "nemotron-twotower-30b-a3b-ep16-train-8k")
    config = next(c for c in MANIFEST["configs"]
                  if c["name"] == entry["config"])
    assert config["reduced"] == CONFIG["reduced"]
    assert config["file"] == "benchmark/configs/joyai-llm-flash-ep32.json"
    listed = [m["name"] for section in ("end_to_end", "per_layer")
              for m in MANIFEST[section] if CELL in m.get("workloads", [])]
    assert listed == ["train_images_per_s", "program_ms.train",
                      "device_idle_share.train", "compile_s",
                      "fit_start_ms.train", "step_mfu.moe_train",
                      "experts_ms.moe_train",
                      "experts_roofline_share.moe_train",
                      "moe_route_ms.moe_train"]
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        if CELL in m.get("workloads", []):       # appended there too
            assert m["workloads"].index(CELL) == 1 + m["workloads"].index(
                "nemotron-twotower-30b-a3b-ep16-train-8k")
    for section in ("configs", "workloads"):
        for item in MANIFEST[section]:
            assert 1 <= len(item["why"]) <= 200, item["name"]


# ---- the adapter and the control ------------------------------------------
@pytest.fixture(scope="module")
def mla():
    return common.load_adapter("lm_train_mla")


def test_the_adapter_renames_lm_trains_parts_in_a_copy_of_its_own(mla):
    lm_train = common.load_adapter("lm_train")
    hybrid = common.load_adapter("lm_train_hybrid")
    assert mla.base is not lm_train and mla.base is not hybrid.base
    assert mla.base.__file__ == lm_train.__file__
    assert issubclass(mla.Cell, mla.base.Cell)
    assert not issubclass(mla.Cell, lm_train.Cell)
    # the other cells' copies keep their names
    assert "lm.conv_op" in lm_train.SCOPES and "lm.ssm" in hybrid.base.SCOPES
    assert lm_train.flops_lm is flops_lm
    # this cell's copy has this stack's
    assert mla.base.SCOPES == mla.SCOPES == (
        "lm.attention", "lm.mtp", "lm.dense_ff", "lm.shared_ff", "moe.route",
        "moe.experts", "lm.head")
    assert mla.INNER_SCOPES == ("lm.attention.latent",)
    assert mla.base.flops_lm is flops_mla
    assert mla.base.group_of is mla.group_of
    assert mla.base.KERNELS == {"ragged-dot": "moe.experts"}
    groups = {mla.group_of(name) for name in (
        "embed", "head", "embedding_norm", "layers.0.input_layernorm",
        "layers.0.attn.q_a_norm", "layers.0.ff.w3", "layers.1.moe.w1",
        "layers.1.moe.router", "layers.1.moe.expert_bias",
        "layers.1.shared.w2", "mtp.merge", "mtp.hnorm")}
    assert groups == set(CONFIG["check"]["grad_rel_l2"]) == set(
        mla.LIMITS_WHY)
    assert mla.decoder_config(CONFIG)["n_routed_experts"] == 256
    assert mla.decoder_config(CONFIG)["vocab_size"] == 129280
    assert "published" not in mla.decoder_config(CONFIG)


def test_the_flash_kernels_are_summed_by_their_traced_name(mla):
    planes = {"/device:TPU:0": {
        "XLA Modules": [("jit_step(1)", 0.0, 9e6), ("jit_step(1)", 1e7, 9e6),
                        ("jit_other(2)", 2e7, 1e6)],
        "XLA Ops": [("%flash_attention.3 = (bf16[128,8192,192]{2,1,0}, "
                     "bf16[128,8192,128]{2,1,0}) custom-call(s32[1]{0} %g",
                     0.0, 2e6),
                    ("%flash_attention.3 = (bf16[128,8192,192]{2,1,0}, "
                     "bf16[128,8192,128]{2,1,0}) custom-call(s32[1]{0} %g",
                     1e7, 4e6),
                    ("flash_attention.12", 3e6, 1e6),
                    ("%fusion.7 = bf16[4,8192] fusion(%flash_attention.3)",
                     4e6, 5e6),
                    ("%flash_attention_like.1 = f32[] add()", 5e6, 5e6)]}}
    assert mla.kernels_ms(planes, "jit_step") == {
        "flash_attention.3": 3.0, "flash_attention.12": 0.5}
    assert mla.kernels_ms({}, "jit_step") == {}


def test_the_decoder_takes_the_configuration_file_as_it_is(mla):
    from tpudl.zoo.decoder import Decoder

    lm = Decoder(mla.decoder_config(CONFIG))
    assert lm.kinds() == {"conv": 0, "attention": 6, "ssm": 0, "dense": 1,
                          "routed": 5, "shared": 5, "mla": 6, "mtp": 1}
    assert (lm.held, lm.vocab_slice, lm.top_k, lm.scaling) == (
        (0, 8), (0, 16160), 8, 2.5)
    assert (lm.q_rank, lm.kv_rank, lm.qk_nope, lm.qk_rope, lm.v_head_dim,
            lm.heads) == (1536, 512, 128, 64, 128, 32)
    assert not lm.tied and lm.theta == 32e6 and lm.eps == 1e-6
    assert lm.mtp == 1 and lm.mtp_weight == 0.3
    assert lm.runs() == [(0, 1), (1, 4)]
    small = common.resolve(MANIFEST, CELL, rehearse=True).config
    toy = Decoder(mla.decoder_config(small))
    assert toy.kinds()["mla"] == 4 and toy.held == (4, 4)
    assert (toy.qk_nope, toy.qk_rope, toy.v_head_dim) == (16, 8, 16)


def test_the_control_rounds_the_scores_operands_and_nothing_else():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudl.zoo import lm_blocks

    flash = lm_blocks.flash_attention
    path = os.path.join(REPO, "benchmark", "controls", "mla_fp8_scores.py")
    with open(path) as f:
        text = f.read()
    assert "lm_blocks.flash_attention = " in text
    assert 'run_name="__main__"' in text and "benchmark" in text
    # importing it runs nothing and changes nothing
    control = common.load_module(path, "mla_fp8_scores_control")
    assert lm_blocks.flash_attention is flash
    x = jnp.asarray(np.random.default_rng(0).standard_normal((64, 8)),
                    jnp.float32)
    rounded = control.e4m3(x)
    err = np.abs(np.asarray(rounded - x)) / np.abs(np.asarray(x)).max()
    assert 1e-3 < err.max() < 2 ** -4    # three mantissa bits, one scale
    np.testing.assert_array_equal(                # straight through
        jax.grad(lambda a: (control.e4m3(a) ** 2).sum())(x), 2 * rounded)


def _run(*argv, script=("benchmark", "run.py")):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, os.path.join(REPO, *script), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "2", *argv],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)


def _facts(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("benchmark: facts ")][-1]
    return json.loads(line[len("benchmark: facts "):])


@pytest.fixture(scope="module")
def rehearsal():
    return _facts(_run("--trace", "1", "--rehearse"))


def test_the_cell_rehearses_and_is_correct_at_toy_widths(rehearsal):
    assert rehearsal["rehearsal"] is True and rehearsal["correct"] is True
    assert rehearsal["compiles_in_window"] == 0
    check = rehearsal["check"]
    assert check["over"] == {}
    assert set(check["grad_rel_l2"]) == set(CONFIG["check"]["grad_rel_l2"])
    lm = rehearsal["lm"]
    for key in ("attention_flops", "mtp_flops", "step_flops",
                "experts_flops", "pairs_held"):
        assert lm[key] > 0, key
    assert lm["pairs_total"] == 3 * 128 * 3      # 2 routed layers + module
    assert lm["device_scope_ms"] == {}           # a CPU has no device plane


def test_the_control_fails_correct_at_toy_widths(rehearsal):
    facts = _facts(_run("--trace", "0", "--rehearse", script=(
        "benchmark", "controls", "mla_fp8_scores.py")))
    assert facts["correct"] is False
    over, sound = facts["check"]["over"], rehearsal["check"]["grad_rel_l2"]
    assert "mla" in over
    assert over["mla"] > 2 * sound["mla"]
    # by one of the limits, not by each: the loss and AdamW are untouched
    for key in ("loss_rel", "update_rel_l2", "moment2_rel_l2"):
        assert key not in over, key


def test_a_program_without_latent_attention_is_refused_at_once(monkeypatch,
                                                               mla):
    from tpudl.zoo import lm_blocks

    spec = common.resolve(MANIFEST, CELL, rehearse=True)
    monkeypatch.delattr(lm_blocks, "mla_op")
    with pytest.raises(SystemExit, match="no latent attention"):
        mla.run(spec, lambda cell: pytest.fail("the cell was driven"))
