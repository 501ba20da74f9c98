"""TFImageTransformer — arbitrary model graph over an image-struct column.

Rebuild of ref: python/sparkdl/transformers/tf_image.py (~L50 class,
~L120 _transform). The reference splices [spImageConverter → user graph →
flattener] into one frozen GraphDef executed per block by TensorFrames;
here the same composition is [sp_image_converter → ingested jax fn →
flatten/restruct] traced into ONE jit program, executed per batch by
``Frame.map_batches`` with mesh data-parallel sharding (SURVEY.md §3.2's
one-native-call-per-block invariant, now one-XLA-program-per-batch).
"""

from __future__ import annotations

import numpy as np

import jax

from tpudl.image import imageIO
from tpudl.image import ops as image_ops
from tpudl.ml.params import (HasInputCol, HasOutputCol, HasOutputMode, Param,
                             TypeConverters, keyword_only)
from tpudl.ml.pipeline import Transformer

__all__ = ["TFImageTransformer"]

OUTPUT_MODES = ("vector", "image")


class ImageBatchWarmup:
    """Mixin: no-fetch warm path for image-batch transformers.

    Requires ``_get_jfn()`` (the fused jitted program), ``batchSize``
    and ``mesh`` on the host class.
    """

    def warmup(self, height, width, nChannels=3, dtype=np.uint8):
        """Compile and warm the fused program for (height, width,
        nChannels) input images WITHOUT any device→host read.

        Warming up by running ``transform`` pays a full pass over the
        frame and ends in a blocking device→host fetch. This method
        instead executes the program once on a synthetic batch and
        discards the device result unread, so a fresh process that
        calls ``warmup(...)`` and then ``transform(frame)`` pays the
        compile up front and fetches exactly once, at the transform's
        end.

        Call with the shape of the frame's images (pre-resize where the
        on-device pipeline resizes: the traced signature is the *input*
        shape). Only the full-batch signature is warmed; a ragged tail
        batch compiles during the transform. Returns ``self``.

        With the AOT program store armed (``TPUDL_COMPILE_AOT``,
        COMPILE.md) this becomes a pure AOT warm call: the program is
        ``lower().compile()``-d from declared abstract shapes — no
        synthetic batch, no real-data trace, no device execution at
        all — and lands in the store, so the NEXT process restores it
        serialized and skips even this compile.
        """
        import os as _os

        from tpudl.frame import frame as _frame

        jfn = self._get_jfn()
        x = np.zeros((self.batchSize, height, width, nChannels),
                     dtype=dtype)
        mesh = self.mesh
        fuse = getattr(self, "fuseSteps", None)
        if fuse is None:
            fuse = _frame._env_int("TPUDL_FRAME_FUSE_STEPS", 1)
        warm_fused = (int(fuse) > 1
                      and _frame.mesh_fuse_ok(self.batchSize, mesh)
                      and _os.environ.get("TPUDL_FRAME_PREFETCH", "1")
                      != "0")
        # match the executor's donation setting, or this warms a
        # program variant the timed window never runs
        donate = _os.environ.get("TPUDL_FRAME_DONATE", "1") != "0"
        from tpudl import compile as _compile

        if _compile.aot_enabled():
            # AOT warm call (ISSUE 15): declared-signature compile
            # through the program store — the executor's dispatch hits
            # these exact keys, and the serialized executables make the
            # next process's warmup a deserialization
            store = _compile.get_program_store()
            store.ensure_restored(block=True)
            # mirror the executor's bucket pick EXACTLY: with a ladder
            # armed the dispatch shape is the rung (mesh: rounded up to
            # the data axis), and a non-rung batchSize drops fusion —
            # warming the raw batchSize would compile a program the
            # timed window never runs
            ladder = _compile.resolve_ladder(None)
            rows = int(self.batchSize)
            if ladder is not None:
                rows = ladder.pick(rows)
                if rows != int(self.batchSize):
                    warm_fused = False
            if mesh is not None:
                from tpudl import mesh as M

                axis = mesh.shape[M.DATA_AXIS]
                pad_shape = ((-(-rows // axis)) * axis,) + x.shape[1:]
                aval = jax.ShapeDtypeStruct(
                    pad_shape, dtype,
                    sharding=M.batch_sharding(mesh,
                                              ndim=len(pad_shape)))
            else:
                aval = jax.ShapeDtypeStruct((rows,) + x.shape[1:],
                                            dtype)
            store.compile_signature(
                jfn, [aval], donate=False,
                bucketed=(ladder is not None and mesh is None))
            if warm_fused:
                fused = _frame._fused_wrapper(jfn, int(fuse), n_args=1,
                                              donate=donate)
                stacked_shape = (int(fuse),) + tuple(aval.shape)
                if mesh is not None:
                    sds = jax.ShapeDtypeStruct(
                        stacked_shape, dtype,
                        sharding=M.stacked_batch_sharding(
                            mesh, ndim=len(stacked_shape)))
                else:
                    sds = jax.ShapeDtypeStruct(stacked_shape, dtype)
                store.compile_signature(fused, [sds], donate=donate)
            return self
        if mesh is not None:
            from tpudl import mesh as M

            x_pad, _ = M.pad_batch(x, mesh.shape[M.DATA_AXIS])
            warm_in = M.transfer_batch([x_pad], mesh)[0]
        else:
            warm_in = x
        jax.block_until_ready(jfn(warm_in))  # compile+execute; unfetched
        # the executor will run the FUSED multi-step program when
        # fuse_steps > 1 — warm that compile too (compiles don't
        # fetch, and a mid-transform compile would land inside the
        # timed window). The mesh path fuses only when the batch
        # shards evenly and the fast path is armed (map_batches'
        # own rule) — warm exactly the variant it will run.
        if warm_fused:
            fused = _frame._fused_wrapper(jfn, int(fuse), n_args=1,
                                          donate=donate)
            xs = np.zeros((int(fuse),) + x.shape, dtype=dtype)
            if mesh is not None:
                xs = M.transfer_batch([xs], mesh, batch_dim=1)[0]
            jax.block_until_ready(fused(xs))
        return self


class TFImageTransformer(ImageBatchWarmup, Transformer, HasInputCol,
                         HasOutputCol, HasOutputMode):
    """Applies a model function to an image column.

    Params (ref spelling kept: tf_image.py ~L50):

    - ``graph``: a ``TFInputGraph`` (ingested TF artifact) **or** any
      jax-traceable callable batch(B,H,W,C) float32 → array.
    - ``inputTensor``/``outputTensor``: tensor names when ``graph`` is a
      multi-tensor ``TFInputGraph``; default its declared input/output.
    - ``channelOrder``: channel order the model expects — 'RGB'
      (keras-style), 'BGR' (caffe-style), 'L' (ref: v1.x channelOrder).
    - ``outputMode``: 'vector' (flattened float vector per row) or
      'image' (restructured image struct column).
    """

    graph = Param(None, "graph", "TFInputGraph or jax-callable model")
    inputTensor = Param(None, "inputTensor", "input tensor name",
                        TypeConverters.toString)
    outputTensor = Param(None, "outputTensor", "output tensor name",
                         TypeConverters.toString)
    channelOrder = Param(None, "channelOrder",
                         "channel order the model expects: RGB, BGR or L",
                         TypeConverters.toChannelOrder)

    @keyword_only
    def __init__(self, *, inputCol=None, outputCol=None, graph=None,
                 inputTensor=None, outputTensor=None, channelOrder="RGB",
                 outputMode="vector", batchSize=64, mesh=None,
                 prefetchDepth=None, prepareWorkers=None, fuseSteps=None,
                 dispatchDepth=None, wireCodec=None, cacheDir=None,
                 deviceCache=None):
        super().__init__()
        self._setDefault(channelOrder="RGB", outputMode="vector")
        self.batchSize = int(batchSize)
        self.mesh = mesh
        kwargs = dict(self._input_kwargs)
        kwargs.pop("batchSize", None)
        kwargs.pop("mesh", None)
        self._set_pipeline_opts(kwargs)
        self.setParams(**kwargs)

    def setParams(self, **kwargs):
        return self._set(**kwargs)

    # -- model-fn assembly -------------------------------------------------
    def _model_fn(self):
        g = self.getOrDefault(self.graph)
        from tpudl.ingest import TFInputGraph

        if isinstance(g, TFInputGraph):
            feeds = [self.getOrDefault(self.inputTensor)] if self.isDefined(
                self.inputTensor) and self.isSet(self.inputTensor) else None
            fetches = [self.getOrDefault(self.outputTensor)] if self.isDefined(
                self.outputTensor) and self.isSet(self.outputTensor) else None
            fn = g.make_fn(feeds, fetches)
            if g.trainable:
                params = g.params
                return lambda x: fn(params, x)
            return fn
        if callable(g):
            return g
        raise TypeError(
            f"graph param must be TFInputGraph or callable, got {type(g).__name__}")

    def _get_jfn(self):
        order = self.getOrDefault(self.channelOrder)
        mode = self.getOutputMode()

        def build():
            model = self._model_fn()

            def fn(batch):
                # fused prologue + model + epilogue: one XLA program
                x = image_ops.sp_image_converter(batch, "BGR", order) \
                    if order != "L" else batch.astype(np.float32)
                y = model(x)
                if isinstance(y, tuple):
                    y = y[0]
                return image_ops.flattener(y) if mode == "vector" else y

            return fn

        return self._cached_jit(
            (self.getOrDefault(self.graph),
             self._paramMap.get(self.inputTensor),
             self._paramMap.get(self.outputTensor), order, mode), build)

    def _transform(self, frame):
        in_col = self.getInputCol()
        out_col = self.getOutputCol()
        mode = self.getOutputMode()
        jfn = self._get_jfn()
        out = frame.map_batches(
            jfn, [in_col], [out_col], batch_size=self.batchSize,
            pack=_pack_image_structs, **self._pipeline_opts())
        if mode == "image":
            structs = [
                imageIO.imageArrayToStruct(np.asarray(a, dtype=np.float32))
                for a in out[out_col]
            ]
            out = out.drop(out_col).with_column(out_col, structs)
        return out


def _pack_image_structs(sl: np.ndarray) -> np.ndarray:
    """image-struct column slice → stacked (B, H, W, C) batch.

    The host-side half of the reference's spImageConverter (bytes→tensor);
    the device-side cast/flip lives in image_ops so it fuses into the jit.
    """
    arrays = []
    for row in sl:
        if row is None:
            raise ValueError("null image row; dropna() the frame first")
        if isinstance(row, dict):
            arrays.append(imageIO.imageStructToArray(row, copy=False))
        else:
            arrays.append(np.asarray(row))
    shapes = {a.shape for a in arrays}
    if len(shapes) > 1:
        raise ValueError(
            f"mixed image shapes {sorted(shapes)} in one column; resize "
            "first (imageIO.resizeImage / createResizeImageUDF)")
    return np.stack(arrays)


# pure function of its slice: the executor's prepare pool may run it for
# different batches concurrently (map_batches checks this marker)
_pack_image_structs.thread_safe = True
# stable cache identity: prepared bytes depend only on the struct
# contents, which the frame fingerprint already covers
_pack_image_structs.cache_token = "image_structs_v1"
