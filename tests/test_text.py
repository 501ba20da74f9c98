"""tpudl.text — tokenizer codec, LM stages, and the tokens/s plane
(ISSUE 19).

Covers the tokenizer contract (determinism, fingerprint, vocab
manifest round trip), the TokenCodec wire layer (u16/i32 selection,
bounds validation, manifest-key round trip through the data registry),
sequence packing (ragged rung-padding, dense chunking, cache-token
material), the lm_dataset warm-replay acceptance (epoch 2: ZERO
re-tokenizations, ZERO wire bytes), the LM transformer trio, the
traceck-armed ragged prompt sweep through LMGenerator (zero retraces),
the SQL UDF surface, serve registration, and the tools/validate_text.py
audit (tier-1-wired here, the validate_shards pattern).

The stages that run the full forward (`LMFeaturizer` / `LMClassifier`
/ apply-parity) skip when :mod:`tpudl.attention` cannot import (jax
builds without top-level ``shard_map``); the decode path
(`LMGenerator`) has no such dependency and is exercised everywhere.
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tpudl import obs
from tpudl.frame import Frame
from tpudl.frame.sql import sql
from tpudl.obs import metrics as obs_metrics
from tpudl.text import (ByteTokenizer, TokenCodec, WordTokenizer,
                        lengths, lm_dataset, load_vocab, pack_dense,
                        pack_ragged, pad_mask, tokenize_pack)
from tpudl.text.tokenizer import (BOS_ID, EOS_ID, PAD_ID, UNK_ID,
                                  tokenizer_from_spec)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _attention_importable() -> bool:
    try:
        import tpudl.attention  # noqa: F401

        return True
    except ImportError:
        return False


needs_attention = pytest.mark.skipif(
    not _attention_importable(),
    reason="tpudl.attention unavailable (jax without top-level "
           "shard_map); decode-path coverage still runs")


@pytest.fixture(autouse=True)
def registry():
    obs_metrics.get_registry().reset()
    yield
    obs_metrics.get_registry().reset()


def _counter(name) -> int:
    return int((obs.snapshot().get(name) or {}).get("value") or 0)


def _tiny_lm(tok, *, max_len=64, dim=32):
    from tpudl.zoo.transformer import TinyCausalLM

    lm = TinyCausalLM(vocab=tok.vocab_size, dim=dim, heads=4, layers=2,
                      max_len=max_len)
    return lm, lm.init(0)


# ---------------------------------------------------------------------------
# tokenizer: determinism, fingerprint, manifest round trip
# ---------------------------------------------------------------------------

class TestTokenizer:
    def test_byte_round_trip_is_lossless(self):
        tok = ByteTokenizer()
        for text in ("hello, world", "naïve • ünïcode", ""):
            ids = tok.encode(text, bos=True, eos=True)
            assert ids.dtype == np.int32
            assert ids[0] == BOS_ID and ids[-1] == EOS_ID
            assert tok.decode(ids) == text

    def test_fingerprint_is_deterministic_and_spec_shaped(self):
        a, b = ByteTokenizer(), ByteTokenizer()
        assert a.fingerprint == b.fingerprint
        assert a.fingerprint != ByteTokenizer(lowercase=True).fingerprint
        assert a.cache_token == f"text.tok:byte:{a.fingerprint}"
        again = tokenizer_from_spec(a.spec())
        assert again.fingerprint == a.fingerprint

    def test_word_build_is_corpus_deterministic(self):
        corpus = ["the cat sat", "the dog sat down", "cat and dog"]
        a = WordTokenizer.build(corpus, size=16)
        b = WordTokenizer.build(list(reversed(corpus)), size=16)
        assert a.tokens == b.tokens  # multiset of the corpus, not order
        assert a.fingerprint == b.fingerprint
        ids = a.encode("the zebra sat")
        assert UNK_ID in ids.tolist()  # OOV maps to <unk>
        assert a.decode(a.encode("the cat sat")) == "the cat sat"

    def test_vocab_manifest_round_trip_and_tamper_detection(self, tmp_path):
        tok = WordTokenizer.build(["pack the batch tight"], size=8)
        path = str(tmp_path / "vocab.json")
        tok.save(path)
        again = load_vocab(path)
        assert again.fingerprint == tok.fingerprint
        assert again.encode("pack").tolist() == tok.encode("pack").tolist()
        doc = json.load(open(path))
        doc["lowercase"] = not doc["lowercase"]  # id-shifting hand edit
        json.dump(doc, open(path, "w"))
        with pytest.raises(ValueError, match="fingerprint mismatch"):
            load_vocab(path)

    def test_load_vocab_rejects_foreign_documents(self, tmp_path):
        path = str(tmp_path / "not_vocab.json")
        json.dump({"mode": "byte"}, open(path, "w"))
        with pytest.raises(ValueError, match="not a tpudl-vocab-v1"):
            load_vocab(path)


# ---------------------------------------------------------------------------
# TokenCodec: wire dtype, bounds, registry round trip
# ---------------------------------------------------------------------------

class TestTokenCodec:
    def test_u16_when_vocab_fits_else_i32(self, monkeypatch):
        monkeypatch.delenv("TPUDL_TEXT_WIRE_DTYPE", raising=False)
        assert TokenCodec(vocab_size=260).wire == "u16"
        assert TokenCodec(vocab_size=70_000).wire == "i32"
        assert TokenCodec().wire == "i32"  # unknown vocab: no u16 proof
        monkeypatch.setenv("TPUDL_TEXT_WIRE_DTYPE", "i32")
        assert TokenCodec(vocab_size=260).wire == "i32"
        # explicit arg beats the env
        assert TokenCodec(vocab_size=260, wire_dtype="u16").wire == "u16"

    def test_encode_restore_round_trip_halves_wire_bytes(self):
        codec = TokenCodec(vocab_size=260)
        batch = np.arange(12, dtype=np.int32).reshape(3, 4)
        wire = codec.encode(batch)
        assert wire.dtype == np.uint16
        assert wire.nbytes * 2 == codec.dense_nbytes(wire)
        assert np.array_equal(codec.decode_array(wire), batch)
        import jax

        dev = np.asarray(jax.jit(codec.prologue)(wire))
        assert dev.dtype == np.int32
        assert np.array_equal(dev, batch)

    def test_encode_validates_ids_loudly(self):
        from tpudl.data.codec import CodecError

        codec = TokenCodec(vocab_size=260)
        with pytest.raises(CodecError, match="out of range"):
            codec.encode(np.array([[5, 300]]))
        with pytest.raises(CodecError, match=">= 0"):
            codec.encode(np.array([[-1]]))
        with pytest.raises(CodecError, match="integer"):
            codec.encode(np.ones((2, 2), np.float32))
        with pytest.raises(CodecError, match="u16 token wire"):
            TokenCodec(vocab_size=70_000, wire_dtype="u16")

    def test_registry_and_manifest_key_round_trip(self):
        from tpudl.data.codec import codec_from_key, resolve_codec

        assert isinstance(resolve_codec("tokens"), TokenCodec)
        codec = TokenCodec(pad_id=0, vocab_size=260)
        again = codec_from_key(list(codec.key()))  # JSON round trip
        assert isinstance(again, TokenCodec)
        assert again.key() == codec.key()
        assert again.wire == codec.wire


# ---------------------------------------------------------------------------
# packing: rung snapping, dense chunking, cache-token material
# ---------------------------------------------------------------------------

class TestPacking:
    def test_pack_ragged_snaps_to_rungs_and_right_pads(self):
        seqs = [np.arange(4, 4 + n, dtype=np.int32) for n in (3, 5, 6)]
        out = pack_ragged(seqs)
        assert out.shape == (3, 8)  # longest 6 -> pow2 rung 8
        assert out.dtype == np.int32
        assert out[0, 3:].tolist() == [PAD_ID] * 5
        assert lengths(out).tolist() == [3, 5, 6]
        capped = pack_ragged(seqs, max_len=4)
        assert capped.shape == (3, 4)  # cap wins over the rung

    def test_pack_dense_chunks_one_stream(self):
        seqs = [np.arange(4, 4 + n, dtype=np.int32) for n in (5, 4, 3)]
        out = pack_dense(seqs, 4)
        assert out.shape == (3, 4)  # 12 ids / seq_len 4
        assert np.array_equal(out.reshape(-1), np.concatenate(seqs))
        assert pack_dense([], 4).shape == (1, 4)  # never zero rows

    def test_tokenize_pack_emits_metrics_and_cache_token(self):
        tok = ByteTokenizer()
        pack = tokenize_pack(tok, seq_len=8, dense=True, eos=True)
        assert tok.fingerprint in pack.cache_token
        assert "dense=True" in pack.cache_token
        assert pack.cache_token != tokenize_pack(
            tok, seq_len=16, dense=True, eos=True).cache_token
        out = pack(np.array(["abc", "defgh"], dtype=object))
        assert out.shape[1] == 8
        assert _counter("text.tokenize.calls") == 1
        assert _counter("text.tokenize.tokens") == 10  # 8 bytes + 2 eos
        assert _counter("text.pack.rows") == out.shape[0]

    def test_pad_mask_matches_lengths(self):
        import jax

        batch = pack_ragged([np.array([5, 6, 7]), np.array([5])])
        mask = np.asarray(jax.jit(pad_mask)(batch))
        assert mask.tolist() == [[1, 1, 1, 0], [1, 0, 0, 0]]


# ---------------------------------------------------------------------------
# acceptance: epoch-2 warm replay — zero re-tokenizations, zero wire
# ---------------------------------------------------------------------------

class TestWarmReplay:
    def test_epoch2_is_zero_tokenize_zero_wire(self):
        frame = Frame({"text": np.array(
            [f"document {i} lorem ipsum dolor" for i in range(32)],
            dtype=object)})
        ds = lm_dataset(frame, "text", ByteTokenizer(), seq_len=16,
                        batch_size=8, device_cache=True)
        for batch in ds.iter_epoch(0):
            np.asarray(batch[0])
        c1 = {k: _counter(k) for k in ("text.tokenize.calls",
                                       "data.wire.bytes_shipped")}
        assert c1["text.tokenize.calls"] == 4  # 32 rows / batch 8
        assert c1["data.wire.bytes_shipped"] > 0
        for batch in ds.iter_epoch(1):
            np.asarray(batch[0])
        c2 = {k: _counter(k) for k in c1}
        # THE ISSUE-19 acceptance: the second epoch re-tokenizes
        # NOTHING and ships NOTHING — resident batches replay from HBM
        assert c2 == c1

    def test_shard_cache_keys_on_tokenizer_fingerprint(self, tmp_path):
        frame = Frame({"text": np.array(
            [f"row {i} content" for i in range(8)], dtype=object)})
        cache = str(tmp_path / "shards")

        def drain(tok):
            ds = lm_dataset(frame, "text", tok, seq_len=8, batch_size=4,
                            cache_dir=cache)
            for batch in ds.iter_epoch(0):
                np.asarray(batch[0])

        drain(ByteTokenizer())
        first = _counter("text.tokenize.calls")
        assert first == 2
        drain(ByteTokenizer())  # same fingerprint: pure shard replay
        assert _counter("text.tokenize.calls") == first
        drain(ByteTokenizer(lowercase=True))  # new vocab: new cache key
        assert _counter("text.tokenize.calls") == first + 2


# ---------------------------------------------------------------------------
# LMGenerator: the decode path (runs on every jax build)
# ---------------------------------------------------------------------------

class TestLMGenerator:
    def _gen(self, tok, lm, w, **kw):
        from tpudl.ml import LMGenerator

        kw.setdefault("maxNew", 4)
        return LMGenerator(inputCol="text", outputCol="gen", model=lm,
                           weights=w, tokenizer=tok, **kw)

    def test_transform_appends_completions_and_counts(self):
        tok = ByteTokenizer()
        lm, w = _tiny_lm(tok)
        gen = self._gen(tok, lm, w)
        frame = Frame({"text": np.array(["abc", "defg", "hi"],
                                        dtype=object)})
        out = gen.transform(frame)
        comps = list(out["gen"])
        assert len(comps) == 3 and all(isinstance(c, str) for c in comps)
        assert _counter("lm.generate.requests") == 3
        assert _counter("lm.generate.tokens") <= 3 * 4

    def test_ragged_batching_matches_single_row_bitwise(self):
        # grouping + batch-rung padding must be invisible: the same
        # prompt generates the SAME completion whether it rides a
        # ragged multi-row transform or a frame of its own
        tok = ByteTokenizer()
        lm, w = _tiny_lm(tok)
        texts = ["abc", "defg", "hi", "jklm", "n", "opqrstu"]
        batched = self._gen(tok, lm, w, batchSize=4).transform(
            Frame({"text": np.array(texts, dtype=object)}))
        single = self._gen(tok, lm, w, batchSize=1)
        for text, got in zip(texts, batched["gen"]):
            alone = single.transform(
                Frame({"text": np.array([text], dtype=object)}))
            assert list(alone["gen"])[0] == got

    def test_missing_model_fails_loudly(self):
        from tpudl.ml import LMGenerator

        gen = LMGenerator(inputCol="text", outputCol="gen")
        with pytest.raises(ValueError, match="model"):
            gen.transform(Frame({"text": np.array(["x"], dtype=object)}))


# ---------------------------------------------------------------------------
# LMFeaturizer / LMClassifier / apply parity (full forward: gated)
# ---------------------------------------------------------------------------

@needs_attention
class TestLMForwardStages:
    def test_featurizer_emits_pooled_vectors(self):
        from tpudl.ml import LMFeaturizer

        tok = ByteTokenizer()
        lm, w = _tiny_lm(tok)
        feat = LMFeaturizer(inputCol="text", outputCol="vec", model=lm,
                            weights=w, tokenizer=tok, batchSize=4)
        out = feat.transform(Frame({"text": np.array(
            ["short", "a much longer row"], dtype=object)}))
        vecs = np.stack(list(out["vec"]))
        assert vecs.shape == (2, 32)
        assert np.isfinite(vecs).all()
        assert _counter("lm.embed.rows") == 2

    def test_classifier_returns_label_strings(self):
        from tpudl.ml import LMClassifier

        tok = ByteTokenizer()
        lm, w = _tiny_lm(tok)
        clf = LMClassifier(inputCol="text", outputCol="label", model=lm,
                           weights=w, tokenizer=tok,
                           classes=["good", "bad"], batchSize=4)
        out = clf.transform(Frame({"text": np.array(
            ["one", "two", "three"], dtype=object)}))
        assert set(out["label"]) <= {"good", "bad"}
        with pytest.raises(ValueError, match="distinct"):
            LMClassifier(inputCol="text", outputCol="l", model=lm,
                         weights=w, tokenizer=tok,
                         classes=["go", "gone"])._class_ids(tok)

    def test_packed_batch_logits_match_single_row_bitwise(self):
        # batch-dim packing parity at ONE seq rung: row i of a [4, S]
        # apply must equal the [1, S] apply of that row, bitwise
        import jax

        tok = ByteTokenizer()
        lm, w = _tiny_lm(tok)
        batch = pack_ragged(tok.encode_batch(
            ["abc", "defgh", "ij", "klmnop"], bos=True))
        fn = jax.jit(lambda t: lm.apply(w, t))
        packed = np.asarray(fn(batch))
        for i in range(batch.shape[0]):
            alone = np.asarray(fn(batch[i:i + 1]))
            assert np.array_equal(packed[i], alone[0])


# ---------------------------------------------------------------------------
# acceptance: traceck-armed ragged prompt sweep — ZERO retraces
# ---------------------------------------------------------------------------

_SWEEP_SCRIPT = r"""
import json, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from tpudl.testing import traceck
from tpudl.frame import Frame
from tpudl.ml import LMGenerator
from tpudl.text import ByteTokenizer
from tpudl.zoo.transformer import TinyCausalLM

tok = ByteTokenizer()
lm = TinyCausalLM(vocab=tok.vocab_size, dim=32, heads=4, layers=2,
                  max_len=64)
gen = LMGenerator(inputCol="text", outputCol="gen", model=lm,
                  weights=lm.init(0), tokenizer=tok, maxNew=4,
                  batchSize=1, promptBuckets="pow2")
base = "abcdefghijklmnopqrstuvwxyzabcdef"

def run(lens):
    frame = Frame({"text": np.array([base[:n] for n in lens],
                                    dtype=object)})
    return list(gen.transform(frame)["gen"])

# warm one prompt per pow2 rung the sweep can hit (+bos: 4, 8, 16, 32)
traceck.reset()
run((3, 7, 15, 31))
warm_counts = traceck.counts()
# the ragged sweep: 8 distinct prompt lengths, every dispatch on a
# warmed (batch rung, prompt rung) program — trace-FREE
sweep = (3, 5, 7, 9, 11, 13, 23, 31)
traceck.reset()
out = run(sweep)
counts = traceck.counts()
json.dump({
    "warm_traces": sum(warm_counts.values()),
    "sweep_traces": sum(counts.values()),
    "sweep_retraces": sum(max(0, v - 1) for v in counts.values()),
    "distinct_lens": len(set(sweep)),
    "rows": len(out),
}, open(sys.argv[1], "w"))
"""


class TestZeroRetracePromptSweep:
    def test_ragged_prompt_sweep_zero_retraces(self, tmp_path):
        """THE ISSUE-19 acceptance: a ragged prompt sweep through
        LMGenerator performs ZERO (re)traces once the rung programs
        are warm — generation cost is decode steps, never compiles."""
        out_path = str(tmp_path / "sweep.json")
        script = str(tmp_path / "sweep.py")
        open(script, "w").write(_SWEEP_SCRIPT)
        env = dict(os.environ)
        env["TPUDL_TRACECK"] = "1"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        r = subprocess.run([sys.executable, script, out_path],
                           capture_output=True, text=True, env=env,
                           timeout=300, cwd=REPO)
        assert r.returncode == 0, r.stderr[-2000:]
        got = json.load(open(out_path))
        assert got["distinct_lens"] >= 6
        assert got["rows"] == 8
        assert got["sweep_traces"] == 0, got
        assert got["sweep_retraces"] == 0, got
        assert got["warm_traces"] >= 1  # the shim really was counting


# ---------------------------------------------------------------------------
# SQL UDFs + serve registration
# ---------------------------------------------------------------------------

class TestTextUDFs:
    def test_generate_udf_through_sql(self):
        from tpudl.udf import register_text_udfs
        from tpudl.udf.registry import get_udf

        tok = ByteTokenizer()
        lm, w = _tiny_lm(tok)
        udfs = register_text_udfs(model=lm, weights=w, tokenizer=tok,
                                  max_new=4, prefix="t19_",
                                  batch_size=4)
        assert [u.name for u in udfs] == ["t19_generate", "t19_embed"]
        assert get_udf("t19_generate") is udfs[0]
        frame = Frame({"prompt": np.array(["abc", "de"], dtype=object)})
        out = sql("SELECT t19_generate(prompt) AS story FROM t",
                  {"t": frame})
        assert len(list(out["story"])) == 2
        assert _counter("udf.t19_generate.calls") == 1
        assert _counter("udf.t19_generate.rows") == 2

    def test_classify_registered_only_with_classes(self):
        from tpudl.udf import register_text_udfs

        tok = ByteTokenizer()
        lm, w = _tiny_lm(tok)
        udfs = register_text_udfs(model=lm, weights=w, tokenizer=tok,
                                  classes=["yes", "no"], prefix="t19c_",
                                  register=False)
        assert [u.name for u in udfs] == ["t19c_generate", "t19c_embed",
                                          "t19c_classify"]


class TestServeRegistration:
    def test_add_generator_files_tokenizer_on_the_entry(self):
        from tpudl.ml import LMGenerator
        from tpudl.serve import ModelRegistry

        tok = ByteTokenizer()
        lm, w = _tiny_lm(tok)
        gen = LMGenerator(inputCol="text", outputCol="gen", model=lm,
                          weights=w, tokenizer=tok, maxNew=4)
        reg = ModelRegistry()
        entry = reg.add_generator("story", gen, slots=2, cache_len=32)
        assert entry.tokenizer is tok
        assert entry.model is lm
        assert reg.get("story") is entry
        with pytest.raises(ValueError, match="fully-bound"):
            reg.add_generator("bad", LMGenerator(inputCol="text",
                                                 outputCol="gen"))


# ---------------------------------------------------------------------------
# tools/validate_text.py — the seventh validator (tier-1-wired)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def validator():
    spec = importlib.util.spec_from_file_location(
        "validate_text", os.path.join(REPO, "tools", "validate_text.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestValidateText:
    def _vocab(self, tmp_path, tok=None):
        path = str(tmp_path / "vocab.json")
        (tok or ByteTokenizer()).save(path)
        return path

    def test_clean_artifacts_validate(self, validator, tmp_path):
        path = self._vocab(tmp_path, WordTokenizer.build(
            ["the pack audits clean"], size=8))
        errs, vocab_size = validator.validate_vocab(path)
        assert errs == []
        assert vocab_size == 4 + 4
        batch = pack_ragged([np.array([4, 5, 6]), np.array([7])])
        npy = str(tmp_path / "batch.npy")
        np.save(npy, batch)
        assert validator.validate_packed(npy, vocab_size) == []

    def test_validator_fingerprint_math_matches_tpudl(self, validator):
        tok = ByteTokenizer()
        assert validator.spec_fingerprint(tok.spec()) == tok.fingerprint

    def test_tampered_vocab_and_bad_batches_flagged(self, validator,
                                                    tmp_path):
        path = self._vocab(tmp_path)
        doc = json.load(open(path))
        doc["lowercase"] = True
        json.dump(doc, open(path, "w"))
        errs, _ = validator.validate_vocab(path)
        assert any("fingerprint mismatch" in e for e in errs)
        interior = np.array([[4, PAD_ID, 5]], dtype=np.int32)
        oob = np.array([[4, 9999]], dtype=np.int32)
        floats = np.ones((2, 2), np.float32)
        for name, arr, msg in (("interior", interior, "interior pad"),
                               ("oob", oob, ">= vocab_size"),
                               ("float", floats, "not integer")):
            npy = str(tmp_path / f"{name}.npy")
            np.save(npy, arr)
            errs = validator.validate_packed(npy, 260)
            assert any(msg in e for e in errs), (name, errs)

    def test_cli_contract(self, validator, tmp_path):
        path = self._vocab(tmp_path)
        batch = str(tmp_path / "b.npy")
        np.save(batch, pack_ragged([np.array([4, 5])]))
        assert validator.main(["validate_text.py", path, batch]) == 0
        assert validator.main(["validate_text.py"]) == 2
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "validate_text.py"),
             path, batch], capture_output=True, text=True, timeout=60)
        assert r.returncode == 0
        assert "OK" in r.stdout
