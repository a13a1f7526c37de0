"""MultilayerPerceptron batch operators.

Re-design of batch/classification/MultilayerPerceptronTrainBatchOp.java
(+ predict) — FeedForwardTrainer over the shared distributed L-BFGS.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from ....common.columnar import DenseBlockColumn, block_weights
from ....common.metrics import get_registry, metrics_enabled
from ....common.mlenv import MLEnvironmentFactory
from ....common.mtable import MTable
from ....common.tracing import trace_span
from ....common.vector import SparseBatch
from ....common.params import ParamInfo, Params, RangeValidator
from ....common.types import AlinkTypes, TableSchema
from ....mapper.base import ModelMapper, OutputColsHelper
from ....model.converters import (ArrayPayload, SimpleModelDataConverter,
                                  decode_array)
from ....params.shared import (HasEpsilonDefaultAs000001, HasFeatureCols,
                               HasL2, HasLabelCol, HasMaxIterDefaultAs100,
                               HasPredictionCol, HasPredictionDetailCol,
                               HasReservedCols, HasSeed, HasVectorCol)
from ...base import BatchOperator
from ...common.ann.mlp import MlpObjFunc, mlp_forward, rung_path, walk_path
from ...common.dataproc.feature_extract import extract_design, resolve_feature_cols
from ...common.linear.base import (LinearTrainInfo, blocked_paths,
                                   count_fit, index_labels, linear_moments)
from ...common.optim.optimizers import OptimParams, optimize
from ..utils.model_map import ModelMapBatchOp


class MlpModelConverter(SimpleModelDataConverter):
    """The model rows. The weights, the means and the deviations are ARRAY
    payloads (``ArrayPayload``: no text is written in a fit; ``str()`` of
    one is the ``encode_array`` text older tables hold, and
    ``decode_array`` reads either)."""

    def serialize_model(self, model):
        meta = Params({"layer_sizes": model["layer_sizes"],
                       "labels": [str(l) for l in model["labels"]],
                       "label_type": model["label_type"],
                       "feature_cols": model["feature_cols"],
                       "vector_col": model["vector_col"],
                       "standardization": model.get("standardization", True)})
        return meta, [ArrayPayload(np.asarray(model[k], np.float64))
                      for k in ("coef", "mean", "std")]

    def deserialize_model(self, meta, data):
        labels = meta._m.get("labels", [])
        lt = meta._m.get("label_type", AlinkTypes.STRING)
        if lt in (AlinkTypes.LONG, AlinkTypes.INT):
            labels = [int(float(v)) for v in labels]
        elif lt in (AlinkTypes.DOUBLE, AlinkTypes.FLOAT):
            labels = [float(v) for v in labels]
        return {"layer_sizes": [int(x) for x in meta._m["layer_sizes"]],
                "labels": labels, "label_type": lt,
                "feature_cols": meta._m.get("feature_cols"),
                "vector_col": meta._m.get("vector_col"),
                "coef": decode_array(data[0]), "mean": decode_array(data[1]),
                "std": decode_array(data[2])}


class MultilayerPerceptronTrainBatchOp(BatchOperator, HasLabelCol, HasFeatureCols,
                                       HasVectorCol, HasMaxIterDefaultAs100,
                                       HasEpsilonDefaultAs000001, HasL2, HasSeed):
    """A vector column held as a ``DenseBlockColumn`` (floats or bytes,
    on the device or not) trains where it lies: moments by the linear
    trainers' one blocked pass, the standardization folded into layer 1,
    two blocked passes a superstep in ``jit_mlp_qn`` (``common/ann/mlp.py``;
    layer 1's products over a table of bytes as the kernels of
    ``kernels/mlp.py`` where the input allows: ``paths["walk"]``; the line
    search's rungs of a block as the kernel of ``kernels/mlp_rungs.py``
    where the input allows: ``paths["rungs"]``, counted by
    ``alink_mlp_rung_blocks_total{walk=}``).
    Any other input is read into host rows, standardized there, and fitted
    by ``jax.grad`` over them. ``get_train_info()`` is the (iter, loss)
    table and answers by key for what the fit went through, as a linear
    fit's does (``LinearTrainInfo``)."""

    LAYERS = ParamInfo("layers", list, "hidden+output sizes, e.g. [8, 3]; "
                       "input size is inferred", optional=False)

    def link_from(self, in_op: BatchOperator):
        t = in_op.get_output_table()
        env = MLEnvironmentFactory.get(self.get_ml_environment_id())
        dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
        vector_col = self.params._m.get("vector_col")
        feature_cols = self.params._m.get("feature_cols")
        label_col = self.get_label_col()
        l2 = float(self.params._m.get("l2", 0.0) or 0.0)
        with trace_span("mlp.fit", cat="mlp", coarse=True) as fit:
            with trace_span("mlp.extract", cat="mlp", coarse=True):
                if not vector_col:
                    feature_cols = resolve_feature_cols(t, feature_cols,
                                                        label_col)
                design = extract_design(t, feature_cols, vector_col, dtype)
                labels, y = index_labels(t.col(label_col))
            col = design.get("X")
            blocked = isinstance(col, DenseBlockColumn)
            with trace_span("mlp.moments", cat="mlp", coarse=True):
                if blocked:
                    w = block_weights(col, None, dtype)
                    mean, std, moments_rows = linear_moments(col, w, env)
                    data = {"X": col, "y": y, "w": w,
                            "scale": (1.0 / std).astype(dtype),
                            "shift": (mean / std).astype(dtype)}
                else:
                    X = col if col is not None else SparseBatch(
                        design["idx"], design["val"], design["dim"]
                    ).to_dense(dtype)
                    mean, std = X.mean(0), X.std(0)
                    std = np.where(std < 1e-12, 1.0, std)
                    moments_rows = None
                    data = {"X": (X - mean) / std, "y": y.astype(dtype),
                            "w": np.ones(len(y), dtype)}
            k = len(labels)
            hidden = [int(h) for h in self.get_layers()]
            if hidden and hidden[-1] == k:
                hidden = hidden[:-1]
            layer_sizes = [int(design["dim"])] + hidden + [k]
            obj = MlpObjFunc(layer_sizes, l2=l2)
            rng = np.random.RandomState(self.get_seed())
            w0 = (rng.randn(obj.dim) * 0.5
                  / np.sqrt(max(layer_sizes[0], 1))).astype(dtype)
            if blocked:
                paths = blocked_paths(col, walk_path(col.blocks, layer_sizes))
                paths["rungs"] = rung_path(col.blocks, layer_sizes, dtype)
            else:
                paths = {"design": "rows", "moments": "host",
                         "pass": "host:grad", "walk": "none", "rungs": "none"}
            went: Dict[str, Any] = {}
            # the seed draws the first weights alone: the program takes
            # them as data, so restarts over seeds compile once
            optim = OptimParams(method="LBFGS", max_iter=self.get_max_iter(),
                                epsilon=self.get_epsilon())
            with trace_span("mlp.optimize", cat="mlp", coarse=True,
                            args={"layers": layer_sizes,
                                  "max_iter": optim.max_iter,
                                  "pass": paths["pass"],
                                  "walk": paths["walk"],
                                  "rungs": paths["rungs"]}):
                coef, curve, steps = optimize(obj, data, optim, env,
                                              warm_start=w0, info=went)
            with trace_span("mlp.model", cat="mlp", coarse=True):
                self._output = MlpModelConverter().save_model({
                    "layer_sizes": layer_sizes, "labels": labels,
                    "label_type": t.schema.type_of(label_col),
                    "feature_cols": feature_cols, "vector_col": vector_col,
                    "coef": coef, "mean": mean, "std": std})
            fit.set(rows=int(t.num_rows), steps=int(steps))
        count_fit("alink_mlp", moments_rows, went, int(steps), col,
                  paths["walk"])
        if metrics_enabled() and "rows_trace" in went:
            # the line passes' blocks by the path their rungs took
            get_registry().inc("alink_mlp_rung_blocks_total",
                               len(went["rows_trace"]) * col.blocks.shape[0],
                               {"walk": paths["rungs"]})
        went.update(loss_curve=np.asarray(curve), steps=int(steps),
                    mean=mean, std=std, rows=int(t.num_rows),
                    moments_rows=moments_rows, paths=paths, l2=l2,
                    layers=layer_sizes, label_values=list(labels))
        self._side_outputs = [LinearTrainInfo(
            MTable({"iter": np.arange(1, len(curve) + 1),
                    "loss": np.asarray(curve, np.float64)}), went)]
        return self


class MlpModelMapper(ModelMapper):
    def __init__(self, model_schema, data_schema, params=None, **kwargs):
        super().__init__(model_schema, data_schema, params, **kwargs)
        self.model = None

    def load_model(self, model_table: MTable):
        self.model = MlpModelConverter().load_model(model_table)

    def map_table(self, data: MTable) -> MTable:
        import jax.numpy as jnp
        m = self.model
        design = extract_design(data, m["feature_cols"], m["vector_col"],
                                np.float64, vector_size=m["layer_sizes"][0])
        X = design["X"] if design["kind"] == "dense" else None
        if X is None:
            X = SparseBatch(design["idx"], design["val"], design["dim"]).to_dense(np.float64)
        elif isinstance(X, DenseBlockColumn):
            X = X.to_rows().astype(np.float64)
        Xs = (X - m["mean"]) / m["std"]
        logits = np.asarray(mlp_forward(jnp.asarray(m["coef"]), jnp.asarray(Xs),
                                        m["layer_sizes"]))
        e = np.exp(logits - logits.max(1, keepdims=True))
        probs = e / e.sum(1, keepdims=True)
        pick = probs.argmax(1)
        preds = np.empty(len(pick), object)
        preds[:] = [m["labels"][i] for i in pick]
        pred_col = self.params._m.get("prediction_col", "pred")
        detail_col = self.params._m.get("prediction_detail_col")
        cols, types, vals = [pred_col], [m["label_type"]], [preds]
        if detail_col:
            details = np.asarray(
                [json.dumps({str(l): float(p) for l, p in zip(m["labels"], row)})
                 for row in probs], object)
            cols.append(detail_col)
            types.append(AlinkTypes.STRING)
            vals.append(details)
        helper = OutputColsHelper(data.schema, cols, types,
                                  self.params._m.get("reserved_cols"))
        return helper.build_output(data, vals)


class MultilayerPerceptronPredictBatchOp(ModelMapBatchOp, HasPredictionCol,
                                         HasPredictionDetailCol, HasReservedCols):
    MAPPER_CLS = MlpModelMapper
