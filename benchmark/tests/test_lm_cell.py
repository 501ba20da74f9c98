"""What the cell ``lfm2-8b-a1b-ep4-train-8k`` adds to the benchmark: the
count of needed work, the token traffic, and the four readers that read the
spans of ``benchmark/conftest.py``."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common, flops_lm, peaks, traffic  # noqa: E402

CONFIG = common.load_json(os.path.join(
    REPO, "benchmark", "configs", "lfm2-8b-a1b-ep4.json"))
TRAFFIC = common.load_json(os.path.join(
    REPO, "benchmark", "traffic", "packed-tokens-8k.json"))
FACTS = {"trace": {"program_ms": 40.0, "program_runs": 50},
         "device_kind": "TPU v5 lite", "devices": 1}


# ---- needed work ----------------------------------------------------------
def test_forward_flops_a_token_at_the_published_widths():
    """ISSUE 28's arithmetic: 432.6 MFLOP a token forward at one held pair a
    token and layer (dense layer 121.6, routed layers outside the experts
    121.7 + 0.5 of routers, experts 88.1, causal attention 33.6, head 67.1)."""
    parts = flops_lm.forward_parts(CONFIG, 8192)
    assert parts["dense_ff"] + parts["conv_op"] / 4 == pytest.approx(
        121.6e6, rel=2e-3)
    assert parts["attention_causal"] == pytest.approx(33.6e6, rel=2e-3)
    assert parts["head"] == pytest.approx(67.1e6, rel=2e-3)
    assert 4 * flops_lm.pair_flops(CONFIG) == pytest.approx(88.1e6, rel=2e-3)
    assert flops_lm.forward_flops_per_token(CONFIG, 8192, 4.0) == (
        pytest.approx(432.6e6, rel=2e-3))
    # a step of 32,768 tokens, one held pair a token and layer: 42.5 TFLOP
    assert flops_lm.step_flops(CONFIG, 32768, 8192, 4 * 32768) == (
        pytest.approx(42.5e12, rel=2e-3))


def test_flops_against_a_hand_count_at_toy_widths():
    cfg = {"hidden_size": 8, "intermediate_size": 16,
           "moe_intermediate_size": 4, "num_attention_heads": 2,
           "num_key_value_heads": 1, "head_dim": 4, "vocab_size": 32,
           "layer_types": ["conv", "full_attention"], "num_dense_layers": 1,
           "num_experts": 2, "published": {"num_experts": 4}}
    parts = flops_lm.forward_parts(cfg, 6)
    assert parts == {
        "conv_op": 2 * 8 * 24 + 2 * 8 * 8,
        "attention_proj": 2 * (2 * 8 * 8) + 2 * (2 * 8 * 4),
        "attention_causal": 2 * 2 * 2 * 4 * 3.5,    # (6 + 1) / 2 keys a query
        "dense_ff": 3 * 2 * 8 * 16,
        "router": 2 * 8 * 4,
        "head": 2 * 8 * 32}
    assert flops_lm.pair_flops(cfg) == 3 * 2 * 8 * 4
    assert flops_lm.routed_layers(cfg) == 1
    assert flops_lm.step_flops(cfg, 12, 6, 5) == 3 * (
        12 * sum(parts.values()) + 5 * 192)
    work = flops_lm.experts_work(cfg, 5)
    assert work["flops"] == 3 * 5 * 192
    # 3 products x 3 passes x (rows x (in + out) + the held experts' weights)
    assert work["bytes"] == 2 * 9 * (5 * (8 + 4) + 2 * 8 * 4)


def test_work_follows_the_pairs_not_the_buffer():
    few = flops_lm.experts_work(CONFIG, 1000)
    many = flops_lm.experts_work(CONFIG, 131072)
    assert many["flops"] == pytest.approx(131.072 * few["flops"])
    assert flops_lm.step_flops(CONFIG, 32768, 8192, 0) < flops_lm.step_flops(
        CONFIG, 32768, 8192, 131072)


# ---- traffic --------------------------------------------------------------
@pytest.fixture(scope="module")
def generator():
    common.load_adapter("lm_train")     # registers packed_tokens on import
    return traffic.GENERATORS["packed_tokens"]


def test_packed_tokens_registers_itself_and_is_seeded(generator):
    small = {**TRAFFIC, **TRAFFIC["rehearse"]}
    a = traffic.generate(small, 3000000019, vocab=128)
    b = traffic.generate(small, 3000000019, vocab=128)
    c = traffic.generate(small, 5, vocab=128)
    assert len(a["ids"]) == 2 and a["ids"][0].shape == (2, 64)
    assert a["ids"][0].dtype == np.int32
    for x, y in zip(a["ids"], b["ids"]):
        np.testing.assert_array_equal(x, y)
    assert any((x != y).any() for x, y in zip(a["ids"], c["ids"]))
    flat = np.concatenate([x.ravel() for x in a["ids"]])
    assert flat.min() >= 0 and flat.max() < 128
    # every seed packs the same documents in another order: as many eos,
    # but for the documents the end of the last batch cuts off
    assert a["documents"] == c["documents"]
    other = np.concatenate([x.ravel() for x in c["ids"]])
    assert abs(int((flat == 0).sum()) - int((other == 0).sum())) <= 2
    assert a["documents"] - 2 <= (flat == 0).sum() <= a["documents"]


def test_packed_tokens_follow_a_chain_a_model_can_learn(generator):
    data = traffic.generate(TRAFFIC, 11, vocab=16384)
    assert len(data["ids"]) == 4 and data["ids"][0].shape == (4, 8192)
    assert 400 < data["median_document"] < 900
    flat = np.concatenate([x.ravel() for x in data["ids"]])
    pairs = {}
    for cur, nxt in zip(flat[:-1], flat[1:]):
        if cur and nxt:
            pairs.setdefault(int(cur), set()).add(int(nxt))
    seen_often = [len(v) for v in pairs.values() if len(v) > 1]
    # an id is followed by its 4 likely successors nine times in ten
    assert np.median(seen_often) <= 6


# ---- the four readers -----------------------------------------------------
def read(metric, facts=FACTS):
    return common.load_reader(metric).read(facts)


def test_readers_read_the_hand_written_spans(lm_train_spans):
    assert read("experts_ms.moe_train") == pytest.approx(
        lm_train_spans["experts_ms"])
    assert read("moe_route_ms.moe_train") == pytest.approx(
        lm_train_spans["moe_route_ms"])
    peak = peaks.peak("TPU v5 lite", "bf16_flops_per_s")
    bw = peaks.peak("TPU v5 lite", "hbm_bytes_per_s")
    assert read("step_mfu.moe_train") == pytest.approx(
        100 * lm_train_spans["step_flops"] / (0.040 * peak))
    least = max(lm_train_spans["experts_flops"] / peak,
                lm_train_spans["experts_bytes"] / bw)
    assert read("experts_roofline_share.moe_train") == pytest.approx(
        100 * least / (lm_train_spans["experts_ms"] / 1e3))
    assert 0 < read("experts_roofline_share.moe_train") < 100
    # four chips: four times the peak to set the same work against
    assert read("step_mfu.moe_train", {**FACTS, "devices": 4}) == (
        pytest.approx(read("step_mfu.moe_train") / 4))


@pytest.mark.parametrize("metric", [
    "step_mfu.moe_train", "experts_ms.moe_train",
    "experts_roofline_share.moe_train", "moe_route_ms.moe_train"])
def test_nothing_to_read_is_none(metric, lm_train_spans, monkeypatch):
    assert read(metric, {"trace": None}) is None
    assert read(metric, {}) is None
    # a fit with no such spans under it (the window's, a parent commit's)
    assert read(metric, {**FACTS, "trace": {
        "program_ms": 40.0, "program_runs": 51}}) is None
    # laid over a program that records no spans at all
    import tpudl.obs.trace as T

    monkeypatch.delattr(T, "traced_fit")
    assert read(metric) is None


def test_a_scope_the_trace_never_filed_reads_nothing(lm_train_spans):
    """``record_device_scopes`` writes a span of no length for a scope it
    found nothing under; a reader leaves the metric out instead of 0."""
    from tpudl.obs import get_tracer
    from tpudl.obs.trace import traced_fit

    tracer = get_tracer()
    t = 1_790_000_200_000_000_000
    fit = tracer.record("train.fit", t, 1_000_000_000)
    for i in range(5):
        step = tracer.record("train.step", t + i * 1_000, 1_000, parent=fit)
        tracer.record("train.step.dispatch", step.start_ns, 500, parent=step)
        tracer.record("device.moe.experts", step.start_ns, 0, parent=fit)
    assert traced_fit(tracer.spans(), 5)["fit"].id == fit.id
    facts = {**FACTS, "trace": {"program_ms": 40.0, "program_runs": 5}}
    assert read("experts_ms.moe_train", facts) is None
    assert read("experts_roofline_share.moe_train", facts) is None
    assert read("step_mfu.moe_train", facts) is None     # no lm.step_work


# ---- the configuration file ----------------------------------------------
def test_configuration_carries_the_cut_beside_the_published_counts():
    assert CONFIG["reduced"] == ["num_hidden_layers", "layer_types",
                                 "num_dense_layers", "num_experts",
                                 "vocab_size"]
    published = {"hidden_size": 2048, "intermediate_size": 7168,
                 "moe_intermediate_size": 1792, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "conv_L_cache": 3,
                 "conv_bias": False, "norm_eps": 1e-5,
                 "norm_topk_prob": True, "num_experts_per_tok": 4,
                 "rope_theta": 1000000, "routed_scaling_factor": 1,
                 "use_expert_bias": True, "max_position_embeddings": 128000,
                 "model_type": "lfm2_moe"}
    for key, value in published.items():
        assert CONFIG[key] == value, key
    assert CONFIG["published"]["num_experts"] == 32
    assert CONFIG["published"]["vocab_size"] == 65536
    assert CONFIG["published"]["layer_types"].count("full_attention") == 6
    assert CONFIG["experts_held"] == [0, CONFIG["num_experts"]]
    assert CONFIG["vocab_slice"] == [0, CONFIG["vocab_size"]]
    for key in ("tie_embedding", "head_dim", "optimizer", "no_document_mask",
                "no_balancing_loss"):
        assert key in CONFIG["assumed"]
    # the optimizer as ISSUE 28 named it, the rate constant
    assert CONFIG["optimizer"] == {"name": "adamw", "learning_rate": 3e-4,
                                   "b1": 0.9, "b2": 0.95,
                                   "weight_decay": 0.1}
    limits = CONFIG["check"]
    assert 0.9 <= limits["route_agreement_min"] < 1
    assert limits["update_rel_l2"] < 2e-3    # under what a lost decay reads
    assert set(limits["grad_rel_l2"]) == {"experts", "routers", "conv",
                                          "attention", "dense_ff", "table",
                                          "norms"}
