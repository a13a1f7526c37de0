"""KMeans batch operators + model.

Re-design of batch/clustering/KMeansTrainBatchOp.java:60-120 and
KMeansPredictBatchOp / common/clustering/kmeans/KMeansModelDataConverter.
"""

from __future__ import annotations

import json
from typing import List, Optional

import numpy as np

from ....common.metrics import get_registry, metrics_enabled
from ....common.mtable import MTable
from ....common.params import ParamInfo, Params, RangeValidator, InValidator
from ....common.tracing import trace_span
from ....common.types import AlinkTypes, TableSchema
from ....mapper.base import ModelMapper, OutputColsHelper
from ....model.converters import SimpleModelDataConverter, decode_array, encode_array
from ....params.shared import (HasFeatureCols, HasMaxIterDefaultAs50,
                               HasPredictionCol, HasReservedCols, HasSeed,
                               HasVectorCol)
from ...base import BatchOperator
from ...common.clustering.kmeans import (as_block_column, assign_table,
                                         kmeans_train)
from ...common.dataproc.feature_extract import extract_design, resolve_feature_cols
from ..utils.model_map import ModelMapBatchOp


class KMeansModelData:
    def __init__(self, centroids: np.ndarray, weights: np.ndarray,
                 distance_type: str, vector_col: Optional[str],
                 feature_cols: Optional[List[str]]):
        self.centroids = centroids
        self.weights = weights
        self.distance_type = distance_type
        self.vector_col = vector_col
        self.feature_cols = feature_cols

    @property
    def k(self):
        return self.centroids.shape[0]


class KMeansModelDataConverter(SimpleModelDataConverter):
    """reference: common/clustering/kmeans/KMeansModelDataConverter.java"""

    def serialize_model(self, m: KMeansModelData):
        meta = Params({"k": int(m.k), "distance_type": m.distance_type,
                       "vector_col": m.vector_col, "feature_cols": m.feature_cols})
        return meta, [encode_array(m.centroids), encode_array(m.weights)]

    def deserialize_model(self, meta: Params, data):
        return KMeansModelData(
            centroids=decode_array(data[0]), weights=decode_array(data[1]),
            distance_type=meta._m.get("distance_type", "EUCLIDEAN"),
            vector_col=meta._m.get("vector_col"),
            feature_cols=meta._m.get("feature_cols"))


class _KMeansParams(HasVectorCol, HasFeatureCols, HasMaxIterDefaultAs50, HasSeed):
    K = ParamInfo("k", int, "number of clusters", default=2,
                  validator=RangeValidator(1, None))
    EPSILON = ParamInfo("epsilon", float, "centroid-movement tolerance", default=1e-4)
    DISTANCE_TYPE = ParamInfo("distance_type", str, default="EUCLIDEAN",
                              validator=InValidator(["EUCLIDEAN", "COSINE"]))
    INIT_MODE = ParamInfo("init_mode", str, default="K_MEANS_PARALLEL",
                          validator=InValidator(["RANDOM", "K_MEANS_PARALLEL"]))


def _design_rows(table: MTable, feature_cols, vector_col, dtype):
    """The table's features as the KMeans programs take them: the dense
    block column itself where the table holds one (it may live on the
    device and no row is touched), else host rows ``(n, d)``."""
    design = extract_design(table, feature_cols, vector_col, dtype)
    if design["kind"] == "dense":
        return design["X"]
    from ....common.vector import SparseBatch
    return SparseBatch(design["idx"], design["val"],
                       design["dim"]).to_dense(dtype)


class KMeansTrainBatchOp(BatchOperator, _KMeansParams):
    def link_from(self, in_op: BatchOperator) -> "KMeansTrainBatchOp":
        with trace_span("kmeans.fit", cat="kmeans", coarse=True) as fit:
            t = in_op.get_output_table()
            vector_col = self.params._m.get("vector_col")
            feature_cols = self.params._m.get("feature_cols")
            if not vector_col:
                feature_cols = resolve_feature_cols(t, feature_cols)
            import jax
            dtype = np.float64 if jax.config.jax_enable_x64 else np.float32
            X = _design_rows(t, feature_cols, vector_col, dtype)
            info = {}
            cents, wts, steps = kmeans_train(
                X, k=self.get_k(), max_iter=self.get_max_iter(),
                tol=self.get_epsilon(), distance_type=self.get_distance_type(),
                init=self.get_init_mode(), seed=self.get_seed(), info=info)
            with trace_span("kmeans.model", cat="kmeans", coarse=True):
                model = KMeansModelData(np.asarray(cents, np.float64),
                                        np.asarray(wts, np.float64),
                                        self.get_distance_type(), vector_col,
                                        feature_cols)
                self._output = KMeansModelDataConverter().save_model(model)
                self._side_outputs = [MTable({"cluster_id": np.arange(model.k),
                                              "weight": model.weights})]
            self._steps = steps
            self._train_info = info
            fit.set(rows=int(t.num_rows), steps=int(steps))
        if metrics_enabled():
            get_registry().inc("alink_kmeans_fits_total", 1)
        return self

    def get_train_info(self) -> dict:
        """What the last fit went through (``kmeans_train``'s ``info``):
        the k-means|| candidates and weights, the initial centroids, and
        per superstep the centroids, cluster weights, inertia and rows."""
        return self._train_info


class KMeansModelMapper(ModelMapper):
    """reference: common/clustering/kmeans/KMeansModelMapper.java"""

    def __init__(self, model_schema, data_schema, params=None, **kwargs):
        super().__init__(model_schema, data_schema, params, **kwargs)
        self.model: Optional[KMeansModelData] = None

    def load_model(self, model_table: MTable):
        self.model = KMeansModelDataConverter().load_model(model_table)

    def get_output_schema(self) -> TableSchema:
        pred_col = self.params._m.get("prediction_col", "cluster_id")
        dist_col = self.params._m.get("prediction_distance_col")
        reserved = self.params._m.get("reserved_cols")
        cols, types = [pred_col], [AlinkTypes.LONG]
        if dist_col:
            cols.append(dist_col)
            types.append(AlinkTypes.DOUBLE)
        return OutputColsHelper(self.data_schema, cols, types, reserved).get_output_schema()

    def map_table(self, data: MTable) -> MTable:
        m = self.model
        X = _design_rows(data, m.feature_cols, m.vector_col, np.float64)
        # the trainer's own blocked distance, block by block
        ids, dists = assign_table(as_block_column(X), m.centroids,
                                  m.distance_type)
        ids = np.asarray(ids, np.int64)
        dists = np.sqrt(np.maximum(np.asarray(dists, np.float64), 0.0)) \
            if m.distance_type == "EUCLIDEAN" else np.asarray(dists, np.float64)
        pred_col = self.params._m.get("prediction_col", "cluster_id")
        dist_col = self.params._m.get("prediction_distance_col")
        reserved = self.params._m.get("reserved_cols")
        cols, types, vals = [pred_col], [AlinkTypes.LONG], [ids]
        if dist_col:
            cols.append(dist_col)
            types.append(AlinkTypes.DOUBLE)
            vals.append(dists)
        return OutputColsHelper(data.schema, cols, types, reserved).build_output(data, vals)


class KMeansPredictBatchOp(ModelMapBatchOp, HasPredictionCol, HasReservedCols):
    MAPPER_CLS = KMeansModelMapper
    PREDICTION_DISTANCE_COL = ParamInfo("prediction_distance_col", str,
                                        "output distance column")
