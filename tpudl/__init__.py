"""tpudl — TPU-native deep learning pipelines.

A ground-up jax/XLA/Flax framework with the capability surface of
`spark-deep-learning` (`sparkdl`): see SURVEY.md for the blueprint and the
per-module docstrings for reference anchors. The public API mirrors the
reference's names (ref: python/sparkdl/__init__.py:~L1-40) so a sparkdl
user finds everything under the same spelling, while execution is fused
jitted programs on a TPU mesh.
"""

import importlib
import os as _os

from tpudl.version import __version__

if _os.environ.get("TPUDL_TRACECK", "0") == "1":
    # recompile-storm sentinel (tpudl.testing.traceck): install the
    # jax.jit counting shim BEFORE any product module binds jax.jit
    # into a decorator/partial/local — import order IS the contract
    from tpudl.testing import traceck as _traceck  # noqa: F401

# symbol → defining module. Extended as layers land; __all__ derives from it
# so star-import never advertises a module that does not exist yet.
_LAZY = {
    "Frame": "tpudl.frame",
    "sql": "tpudl.frame",
    "register_udf": "tpudl.udf",
    # L5 product surface (ref: sparkdl/__init__.py __all__)
    "DeepImageFeaturizer": "tpudl.ml",
    "DeepImagePredictor": "tpudl.ml",
    "TFImageTransformer": "tpudl.ml",
    "TFTransformer": "tpudl.ml",
    "KerasTransformer": "tpudl.ml",
    "KerasImageFileTransformer": "tpudl.ml",
    "Pipeline": "tpudl.ml",
    "PipelineModel": "tpudl.ml",
    "TFInputGraph": "tpudl.ingest",
    "KerasImageFileEstimator": "tpudl.ml.estimator",
    "ParamGridBuilder": "tpudl.ml.tuning",
    "CrossValidator": "tpudl.ml.tuning",
    "LogisticRegression": "tpudl.ml",
    "registerKerasImageUDF": "tpudl.udf.keras_image_model",
    "GraphFunction": "tpudl.ingest",
    "IsolatedSession": "tpudl.ingest",
    # preemption-survivable job runtime (JOBS.md)
    "JobSpec": "tpudl.jobs",
    "JobRuntime": "tpudl.jobs",
    "RetryPolicy": "tpudl.jobs",
    # wire-aware dataset subsystem (DATA.md)
    "Dataset": "tpudl.data",
    "U8Codec": "tpudl.data",
    "BF16Codec": "tpudl.data",
    "ShardCache": "tpudl.data",
    # text subsystem: tokenizer codec + LM pipeline stages (TEXT.md)
    "ByteTokenizer": "tpudl.text",
    "WordTokenizer": "tpudl.text",
    "TokenCodec": "tpudl.text",
    "lm_dataset": "tpudl.text",
    "LMFeaturizer": "tpudl.ml",
    "LMGenerator": "tpudl.ml",
    "LMClassifier": "tpudl.ml",
    # long-context / sequence parallelism (TPU-native addition)
    "ring_attention": "tpudl.attention",
    "shard_sequence": "tpudl.attention",
    "flash_attention": "tpudl.pallas_ops",
    "TinyCausalLM": "tpudl.zoo.transformer",
    "Decoder": "tpudl.zoo.decoder",
}

__all__ = ["__version__", *_LAZY]


def __getattr__(name):
    # Lazy re-exports: keep `import tpudl` light (no TF, no model zoo) until
    # a symbol is actually used.
    if name in _LAZY:
        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(f"module 'tpudl' has no attribute {name!r}")


def __dir__():
    return sorted(__all__)
