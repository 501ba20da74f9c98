"""What the cell ``nemotron-twotower-30b-a3b-ep16-train-8k`` adds to the
benchmark: the count of needed work of a ``nemotron_h`` stack, the
configuration with its cut, the adapter that renames ``lm_train``'s parts,
and the control. That the cell resolves and rehearses is tested where every
cell's is, in ``test_benchmark.py``, unedited: it reads ``BENCHMARK.json``."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common, flops_hybrid, flops_lm  # noqa: E402

CELL = "nemotron-twotower-30b-a3b-ep16-train-8k"
MANIFEST = common.load_json(os.path.join(REPO, "BENCHMARK.json"))
CONFIG = common.load_json(os.path.join(
    REPO, "benchmark", "configs", "nemotron-twotower-30b-a3b-ep16.json"))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# ---- needed work ----------------------------------------------------------
def test_forward_flops_a_token_at_the_published_widths():
    """ISSUE 32's arithmetic: 588.9 MFLOP a token forward at 0.375 held
    pairs a token and routed layer (3 Mamba-2 layers 242.7, 3 routed
    layers 144.2 = shared 119.7 + held pairs 22.5 + routers 2.1,
    attention 113.9, head 88.1). This count reads **588.8**: the issue
    rounded the mixers up (80.87 a layer is 242.6 for three: in-projection
    55.39, out-projection 22.02, convolution 0.05, scan 3.41) and summed
    rounded parts; nothing is counted otherwise."""
    parts = flops_hybrid.forward_parts(CONFIG, 8192)
    ssm = parts["ssm_proj"] + parts["ssm_conv"] + parts["ssm_scan"]
    assert ssm == pytest.approx(242.7e6, rel=1e-3)
    assert ssm / 3 == pytest.approx(55.39e6 + 22.02e6 + 0.05e6 + 3.41e6,
                                    rel=1e-3)
    assert flops_hybrid.scan_flops_per_token(CONFIG) == (
        2 * 8 * 128 * 128 + 2 * 64 * 128 * 64 + 4 * 64 * 64 * 128)
    assert parts["attention_proj"] + parts["attention_causal"] == (
        pytest.approx(113.9e6, rel=1e-3))
    assert parts["shared_ff"] == pytest.approx(119.7e6, rel=1e-3)
    assert parts["router"] == pytest.approx(2.1e6, rel=2e-2)
    assert parts["head"] == pytest.approx(88.1e6, rel=1e-3)
    assert 3 * 0.375 * flops_hybrid.pair_flops(CONFIG) == pytest.approx(
        22.5e6, rel=3e-3)
    per_token = flops_hybrid.forward_flops_per_token(CONFIG, 8192, 3 * 0.375)
    assert per_token == pytest.approx(588.9e6, rel=5e-4)
    assert per_token == pytest.approx(588.8e6, rel=1e-4)
    # a step of 32,768 tokens at that load: 57.9 TFLOP
    assert flops_hybrid.step_flops(
        CONFIG, 32768, 8192, round(3 * 0.375 * 32768)) == pytest.approx(
            57.9e12, rel=1e-3)


def test_flops_against_a_hand_count_at_toy_widths():
    cfg = {"hidden_size": 8, "hybrid_override_pattern": "ME*M",
           "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
           "mamba_num_heads": 4, "mamba_head_dim": 2, "n_groups": 2,
           "ssm_state_size": 3, "conv_kernel": 4, "chunk_size": 5,
           "moe_intermediate_size": 6,
           "moe_shared_expert_intermediate_size": 10, "n_shared_experts": 1,
           "n_routed_experts": 2, "vocab_size": 32,
           "published": {"n_routed_experts": 16}}
    scan = 2 * 2 * 5 * 3 + 2 * 4 * 5 * 2 + 2 * 2 * 4 * 2 * 3
    assert flops_hybrid.scan_flops_per_token(cfg) == scan
    parts = flops_hybrid.forward_parts(cfg, 6)
    assert parts == {
        # d_in 8, conv channels 8 + 2 x 2 x 3 = 20, in-projection to 8 + 20 + 4
        "ssm_proj": 2 * (2 * 8 * 32 + 2 * 8 * 8),
        "ssm_conv": 2 * 2 * 4 * 20,
        "ssm_scan": 2 * scan,
        "attention_proj": 2 * (2 * 8 * 8) + 2 * (2 * 8 * 4),
        "attention_causal": 2 * 2 * 2 * 4 * 3.5,    # (6 + 1) / 2 keys a query
        "shared_ff": 2 * 2 * 8 * 10,
        "router": 2 * 8 * 16,
        "head": 2 * 8 * 32}
    assert flops_hybrid.pair_flops(cfg) == 2 * 2 * 8 * 6
    assert flops_hybrid.routed_layers(cfg) == 1
    assert flops_hybrid.step_flops(cfg, 12, 6, 5) == 3 * (
        12 * sum(parts.values()) + 5 * 192)
    work = flops_hybrid.experts_work(cfg, 5)
    assert work["flops"] == 3 * 5 * 192
    # 2 products x 3 passes x (rows x (in + out) + the held experts' weights)
    assert work["bytes"] == 2 * 6 * (5 * (8 + 6) + 2 * 8 * 6)
    ssm = flops_hybrid.ssm_work(cfg, 12)
    assert ssm["flops"] == 3 * 2 * 12 * scan
    # a pass reads x, B, C and writes y in two bytes, and reads Δ in four
    assert ssm["bytes"] == 3 * 2 * 12 * (2 * (2 * 8 + 2 * 6) + 4 * 4)


def test_the_two_counts_share_their_signatures():
    """``lm_train``'s ``Cell`` calls either module through the same two
    functions; the hybrid count follows the pairs too."""
    import inspect

    for fn in ("step_flops", "experts_work", "forward_flops_per_token"):
        assert (inspect.signature(getattr(flops_hybrid, fn))
                == inspect.signature(getattr(flops_lm, fn))), fn
    few = flops_hybrid.experts_work(CONFIG, 1000)
    many = flops_hybrid.experts_work(CONFIG, 36864)
    assert many["flops"] == pytest.approx(36.864 * few["flops"])
    assert flops_hybrid.step_flops(CONFIG, 32768, 8192, 0) < (
        flops_hybrid.step_flops(CONFIG, 32768, 8192, 36864))
    # the scan is 4% of a mixer's needed work: projections are the rest
    parts = flops_hybrid.forward_parts(CONFIG, 8192)
    assert 0.03 < parts["ssm_scan"] / (
        parts["ssm_scan"] + parts["ssm_proj"]) < 0.05


# ---- the configuration file ----------------------------------------------
def test_configuration_carries_the_cut_beside_the_published_counts():
    assert CONFIG["reduced"] == ["num_hidden_layers",
                                 "hybrid_override_pattern",
                                 "n_routed_experts", "vocab_size"]
    assert CONFIG["num_hidden_layers"] == 7 == len(
        CONFIG["hybrid_override_pattern"])
    assert CONFIG["hybrid_override_pattern"] == "MEMEM*E"
    published = CONFIG["published"]
    assert published["hybrid_override_pattern"].startswith("MEMEM*E")
    assert len(published["hybrid_override_pattern"]) == 52 == published[
        "num_hidden_layers"]
    assert [published["hybrid_override_pattern"].count(c) for c in "ME*"] == [
        23, 23, 6]
    assert published["n_routed_experts"] == 128 == 16 * CONFIG[
        "n_routed_experts"]
    assert published["vocab_size"] == 131072 == 8 * CONFIG["vocab_size"]
    assert CONFIG["experts_held"] == [0, CONFIG["n_routed_experts"]]
    assert CONFIG["vocab_slice"] == [0, CONFIG["vocab_size"]]
    # no width is cut
    widths = {"hidden_size": 2688, "head_dim": 128, "num_attention_heads": 32,
              "num_key_value_heads": 2, "mamba_num_heads": 64,
              "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
              "conv_kernel": 4, "chunk_size": 128, "expand": 2,
              "moe_intermediate_size": 1856, "intermediate_size": 1856,
              "moe_shared_expert_intermediate_size": 3712,
              "num_experts_per_tok": 6, "n_shared_experts": 1,
              "routed_scaling_factor": 2.5, "tie_word_embeddings": False,
              "mlp_hidden_act": "relu2", "model_type": "nemotron_h"}
    for key, value in widths.items():
        assert CONFIG[key] == value, key
    for key in ("no_positions_in_attention", "optimizer", "no_document_mask",
                "no_balancing_loss", "initialisation",
                "renormalisation_epsilon", "lone_share"):
        assert key in CONFIG["assumed"]
    # what of the published model is left out is said, not guessed
    for word in ("denoising tower", "adaLN", "block-diffusion", "not built",
                 "not guessed"):
        assert word in CONFIG["not_built"], word
    assert "16 chips" in CONFIG["deployment"]
    assert "6,144" in CONFIG["deployment"] and "1,536" in CONFIG["deployment"]
    assert CONFIG["optimizer"] == {"name": "adamw", "learning_rate": 3e-4,
                                   "b1": 0.9, "b2": 0.95,
                                   "weight_decay": 0.1}
    limits = CONFIG["check"]
    assert 0.9 <= limits["route_agreement_min"] < 1
    assert limits["update_rel_l2"] < 2e-3    # under what a lost decay reads
    assert set(limits["grad_rel_l2"]) == {"ssm", "attention", "experts",
                                          "routers", "shared_ff", "table",
                                          "head", "norms"}
    assert all(0 < v <= 0.05 for v in limits["grad_rel_l2"].values())
    # 15-30 steps a window: the dropped intervals leave most of them
    assert CONFIG["drop_intervals"] + 2 <= 15
    assert "PLACEHOLDER" not in limits["why"]


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the catalog beside the model-configs guide")
def test_every_number_of_the_catalogs_config_is_in_the_file():
    import json

    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f if "TwoTower" in line)
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG[key] != value and CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key


def test_manifest_lists_the_cell_under_the_nine_metrics_named():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert entry == {**entry, "config": "nemotron-twotower-30b-a3b-ep16",
                     "traffic": "packed-tokens-8k", "chips": 1}
    assert MANIFEST["workloads"][-1] is entry    # appended, nothing moved
    assert MANIFEST["configs"][-1]["name"] == entry["config"]
    listed = [m["name"] for section in ("end_to_end", "per_layer")
              for m in MANIFEST[section] if CELL in m.get("workloads", [])]
    assert listed == ["train_images_per_s", "program_ms.train",
                      "device_idle_share.train", "compile_s",
                      "fit_start_ms.train", "step_mfu.moe_train",
                      "experts_ms.moe_train",
                      "experts_roofline_share.moe_train",
                      "moe_route_ms.moe_train"]
    for section in ("configs", "workloads"):
        for item in MANIFEST[section]:
            assert 1 <= len(item["why"]) <= 200, item["name"]


# ---- the adapter and the control ------------------------------------------
@pytest.fixture(scope="module")
def hybrid():
    return common.load_adapter("lm_train_hybrid")


def test_the_adapter_renames_lm_trains_parts_in_a_copy_of_its_own(hybrid):
    lm_train = common.load_adapter("lm_train")
    assert hybrid.base is not lm_train
    assert hybrid.base.__file__ == lm_train.__file__
    assert issubclass(hybrid.Cell, hybrid.base.Cell)
    assert not issubclass(hybrid.Cell, lm_train.Cell)
    # LFM2's copy keeps LFM2's names
    assert "lm.conv_op" in lm_train.SCOPES
    assert lm_train.flops_lm is flops_lm
    assert lm_train.group_of("layers.1.ff.w1") == "dense_ff"
    # this cell's copy has this stack's
    assert hybrid.base.SCOPES == hybrid.SCOPES == (
        "lm.ssm", "lm.attention", "lm.shared_ff", "moe.route", "moe.experts",
        "lm.head")
    assert hybrid.INNER_SCOPES == ("lm.ssm.scan",)
    assert hybrid.base.flops_lm is flops_hybrid
    assert hybrid.base.group_of is hybrid.group_of
    assert hybrid.base.KERNELS == {"ragged-dot": "moe.experts"}
    groups = {hybrid.group_of(name) for name in (
        "embed", "head", "embedding_norm", "layers.0.norm",
        "layers.0.ssm.A_log", "layers.0.ssm.norm", "layers.5.attn.q_proj",
        "layers.1.moe.w1", "layers.1.moe.router", "layers.1.moe.expert_bias",
        "layers.1.shared.w2")}
    assert groups == set(CONFIG["check"]["grad_rel_l2"]) == set(
        hybrid.LIMITS_WHY)
    assert hybrid.decoder_config(CONFIG)["n_routed_experts"] == 128
    assert hybrid.decoder_config(CONFIG)["vocab_size"] == 131072
    assert "published" not in hybrid.decoder_config(CONFIG)


def test_the_decoder_takes_the_configuration_file_as_it_is(hybrid):
    from tpudl.zoo.decoder import Decoder

    lm = Decoder(hybrid.decoder_config(CONFIG))
    assert lm.kinds() == {"conv": 0, "attention": 1, "ssm": 3, "dense": 0,
                          "routed": 3, "shared": 3}
    assert (lm.held, lm.vocab_slice, lm.top_k, lm.scaling) == (
        (0, 8), (0, 16384), 6, 2.5)
    assert (lm.ssm_heads, lm.ssm_head_dim, lm.ssm_groups, lm.ssm_state,
            lm.chunk, lm.taps) == (64, 64, 8, 128, 128, 4)
    assert not lm.tied and lm.theta is None
    assert lm.float32_leaves == (".A_log", ".dt_bias", ".D")
    small = common.resolve(MANIFEST, CELL, rehearse=True).config
    toy = Decoder(hybrid.decoder_config(small))
    # the rehearsal keeps what the reference cannot read from shapes
    assert (toy.head_dim, toy.ssm_groups) == (lm.head_dim, lm.ssm_groups)
    assert toy.kinds()["ssm"] == 2 and toy.held == (4, 4)


def test_the_control_lowers_the_scans_precision_and_nothing_else():
    import jax.numpy as jnp

    from tpudl.zoo import lm_blocks

    assert lm_blocks.SCAN_DTYPE == jnp.float32
    path = os.path.join(REPO, "benchmark", "controls", "hybrid_bf16_scan.py")
    with open(path) as f:
        text = f.read()
    assert "lm_blocks.SCAN_DTYPE = jnp.bfloat16" in text
    assert 'run_name="__main__"' in text and "benchmark" in text
    # importing it runs nothing and changes nothing
    common.load_module(path, "hybrid_bf16_scan_control")
    assert lm_blocks.SCAN_DTYPE == jnp.float32
