"""Tree-family batch operators: GBDT, RandomForest, DecisionTree
(classification + regression).

Re-design of batch/classification/{GbdtTrainBatchOp, RandomForestTrainBatchOp,
DecisionTreeTrainBatchOp} (+Reg variants, + predict ops) over the
histogram-parallel device builder (common/tree/).
"""

from __future__ import annotations

import functools
import json
from typing import List, Optional

import numpy as np

from ....common.columnar import DenseBlockColumn, RowBlockColumn
from ....common.metrics import get_registry, metrics_enabled
from ....common.mtable import MTable
from ....common.params import ParamInfo, Params, RangeValidator
from ....common.types import AlinkTypes, TableSchema
from ....mapper.base import ModelMapper, OutputColsHelper
from ....model.converters import (SimpleModelDataConverter, decode_array,
                                  encode_array)
from ....params.shared import (HasFeatureCols, HasLabelCol, HasPredictionCol,
                               HasPredictionDetailCol, HasReservedCols, HasSeed,
                               HasVectorCol, HasWeightCol)
from ....common.tracing import trace_span
from ...base import BatchOperator
from ...common.dataproc.feature_extract import extract_design, resolve_feature_cols
from ...common.tree.hist import (bins_to_thresholds, thresholds_as,
                                 tree_apply_values)
from ...common.tree.trainers import TreeTrainParams, forest_train, gbdt_train
from ..utils.model_map import ModelMapBatchOp


class TreeModelData:
    def __init__(self, algo: str, is_regression: bool, max_depth: int,
                 features: np.ndarray, thresholds: np.ndarray,
                 leaf_values: np.ndarray, base_score: float, learning_rate: float,
                 labels: List, feature_cols: Optional[List[str]],
                 vector_col: Optional[str], label_type: str = AlinkTypes.STRING,
                 split_masks: Optional[np.ndarray] = None,
                 cat_cols: Optional[List[str]] = None,
                 cat_vocabs: Optional[dict] = None,
                 importances: Optional[np.ndarray] = None):
        self.algo = algo
        self.is_regression = is_regression
        self.max_depth = max_depth
        self.features = features          # (T, 2^d - 1) int
        self.thresholds = thresholds      # (T, 2^d - 1) float
        self.leaf_values = leaf_values    # (T, 2^d) or (T, 2^d, k)
        self.base_score = base_score
        self.learning_rate = learning_rate
        self.labels = labels
        self.feature_cols = feature_cols
        self.vector_col = vector_col
        self.label_type = label_type
        # categorical support (reference seriestree/CategoricalSplitter):
        self.split_masks = split_masks    # (T, 2^d - 1, n_bins) bool or None
        self.cat_cols = cat_cols or []    # feature col names that are categorical
        self.cat_vocabs = cat_vocabs or {}  # col -> [category strings] (code = index)
        self.importances = importances    # (F,) summed split gain or None


class TreeModelDataConverter(SimpleModelDataConverter):
    """reference: common/tree/TreeModelDataConverter.java"""

    def serialize_model(self, m: TreeModelData):
        meta = Params({
            "algo": m.algo, "is_regression": m.is_regression,
            "max_depth": m.max_depth, "base_score": m.base_score,
            "learning_rate": m.learning_rate,
            "labels": [str(l) for l in m.labels], "label_type": m.label_type,
            "feature_cols": m.feature_cols, "vector_col": m.vector_col,
            "cat_cols": m.cat_cols, "cat_vocabs": m.cat_vocabs})
        blobs = [encode_array(m.features), encode_array(m.thresholds),
                 encode_array(m.leaf_values)]
        if m.split_masks is not None:
            blobs.append(encode_array(m.split_masks.astype(np.int8)))
        if m.importances is not None:
            if m.split_masks is None:
                blobs.append(encode_array(
                    np.zeros((0,), np.int8)))  # keep blob positions fixed
            blobs.append(encode_array(np.asarray(m.importances, np.float64)))
        return meta, blobs

    def deserialize_model(self, meta, data):
        labels = meta._m.get("labels", [])
        lt = meta._m.get("label_type", AlinkTypes.STRING)
        if lt in (AlinkTypes.LONG, AlinkTypes.INT):
            labels = [int(float(v)) for v in labels]
        elif lt in (AlinkTypes.DOUBLE, AlinkTypes.FLOAT):
            labels = [float(v) for v in labels]
        split_masks = (decode_array(data[3], np.int8).astype(bool)
                       if len(data) > 3 and decode_array(data[3]).size
                       else None)
        importances = decode_array(data[4]) if len(data) > 4 else None
        return TreeModelData(
            meta._m["algo"], bool(meta._m["is_regression"]),
            int(meta._m["max_depth"]),
            decode_array(data[0], np.int64), decode_array(data[1]),
            decode_array(data[2]), float(meta._m.get("base_score", 0.0)),
            float(meta._m.get("learning_rate", 1.0)), labels,
            meta._m.get("feature_cols"), meta._m.get("vector_col"), lt,
            split_masks=split_masks, cat_cols=meta._m.get("cat_cols"),
            cat_vocabs=meta._m.get("cat_vocabs"), importances=importances)


class _TreeTrainParamsMixin(HasLabelCol, HasFeatureCols, HasVectorCol,
                            HasWeightCol, HasSeed):
    NUM_TREES = ParamInfo("num_trees", int, default=100,
                          validator=RangeValidator(1, None))
    MAX_DEPTH = ParamInfo("max_depth", int, default=5,
                          validator=RangeValidator(1, 14))
    MAX_BINS = ParamInfo("max_bins", int, default=64,
                         validator=RangeValidator(2, 256))
    MIN_SAMPLES_PER_LEAF = ParamInfo("min_samples_per_leaf", int, default=2)
    LEARNING_RATE = ParamInfo("learning_rate", float, default=0.3)
    SUBSAMPLING_RATIO = ParamInfo("subsampling_ratio", float, default=1.0)
    FEATURE_SUBSAMPLING_RATIO = ParamInfo("feature_subsampling_ratio", float,
                                          default=1.0)
    REG_LAMBDA = ParamInfo("reg_lambda", float, default=1.0)
    CATEGORICAL_COLS = ParamInfo("categorical_cols", list, default=None)


def _encode_feature_matrix(t: MTable, feature_cols, cat_cols):
    """(X, cat_mask, cat_vocabs): categorical columns ordinal-encode via a
    sorted per-column vocabulary (code = vocab index, stored in the model
    for serving); numeric columns pass through."""
    n = t.num_rows
    cat_set = set(cat_cols)
    X = np.empty((n, len(feature_cols)), np.float64)
    vocabs = {}
    for j, c in enumerate(feature_cols):
        col = t.col(c)
        if c in cat_set:
            vocab = sorted({str(v) for v in col})
            vocabs[c] = vocab
            lut = {v: i for i, v in enumerate(vocab)}
            X[:, j] = [lut[str(v)] for v in col]
        else:
            X[:, j] = np.asarray(col, np.float64)
    cat_mask = np.asarray([c in cat_set for c in feature_cols], bool)
    return X, cat_mask, vocabs


def _extract_xy(op, t: MTable, regression: bool):
    vector_col = op.params._m.get("vector_col")
    feature_cols = op.params._m.get("feature_cols")
    cat_cols = list(op.params._m.get("categorical_cols") or [])
    label_col = op.get_label_col()
    weight_col = op.params._m.get("weight_col")
    cat_mask, vocabs = None, {}
    if not vector_col:
        feature_cols = resolve_feature_cols(
            t, feature_cols, label_col, exclude=[weight_col] if weight_col else [])
        for c in cat_cols:                 # string cols aren't numeric-resolvable
            if c not in feature_cols:
                feature_cols = feature_cols + [c]
        X, cat_mask, vocabs = _encode_feature_matrix(t, feature_cols, cat_cols)
        if not cat_mask.any():
            cat_mask = None
    else:
        if cat_cols:
            raise ValueError("categorical_cols requires feature_cols input "
                             "(vector input has no column identity)")
        design = extract_design(t, feature_cols, vector_col, np.float64)
        # a DenseBlockColumn comes back as itself: the trainer reads it
        # where it lies
        X = design["X"] if design["kind"] == "dense" else None
        if X is None:
            from ....common.vector import SparseBatch
            X = SparseBatch(design["idx"], design["val"],
                            design["dim"]).to_dense(np.float64)
    raw = t.col(label_col)
    label_type = t.schema.type_of(label_col)
    if regression:
        labels = []
        y = raw if isinstance(raw, RowBlockColumn) else np.asarray(
            raw, np.float64)
    else:
        labels, y = _encode_labels(raw)
        if label_type in (AlinkTypes.LONG, AlinkTypes.INT):
            labels = [int(float(v)) for v in labels]
        elif label_type in (AlinkTypes.DOUBLE, AlinkTypes.FLOAT):
            labels = [float(v) for v in labels]
    w = t.col(weight_col) if weight_col else None
    if w is not None and not isinstance(w, RowBlockColumn):
        w = np.asarray(w, np.float64)
    return (X, y, w, labels, feature_cols, vector_col, label_type,
            cat_mask if not vector_col else None, cat_cols, vocabs)


def _encode_labels(raw):
    """``(labels, y)``: the distinct label values as strings, sorted, and
    each row's index among them, with no Python a row. A device-resident
    ``RowBlockColumn`` is read where it lies (its distinct values by one
    reduction, at most two of them) and ``y`` comes back laid out as it
    is."""
    if isinstance(raw, RowBlockColumn):
        lo, hi, others = (float(v) for v in _label_pair(raw.blocks,
                                                        raw.n_rows))
        if others:
            raise ValueError("a blocked label column must hold at most two "
                             f"distinct values; found one besides {lo} and "
                             f"{hi}")
        vals = [lo] if lo == hi else sorted([lo, hi], key=str)
        y = (raw.blocks == vals[-1]).astype(raw.blocks.dtype)
        return [str(v) for v in vals], y
    arr = np.asarray(raw)
    try:
        uniq, inv = np.unique(arr, return_inverse=True)
    except TypeError:                       # mixed objects: row by row
        labels = sorted({str(v) for v in raw})
        return labels, np.asarray([labels.index(str(v)) for v in raw],
                                  np.float64)
    strs = [str(v) for v in uniq]
    labels = sorted(set(strs))
    rank = np.asarray([labels.index(s) for s in strs], np.float64)
    return labels, rank[inv.reshape(-1)]


@functools.lru_cache(maxsize=None)
def _label_pair_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def pair(b, n_rows):
        here = jnp.arange(b.size).reshape(b.shape) < n_rows
        lo = jnp.where(here, b, jnp.inf).min()
        hi = jnp.where(here, b, -jnp.inf).max()
        return lo, hi, (here & (b != lo) & (b != hi)).sum()
    return pair


def _label_pair(blocks, n_rows: int):
    """(least, greatest, rows that are neither) of a blocked per-row
    column's first ``n_rows`` values, in one fused reduction where it
    lies (no per-row temporary)."""
    return _label_pair_fn()(blocks, n_rows)


def _model_info_table(m: "TreeModelData") -> MTable:
    """Model summary incl. gain-based feature importances (reference
    GbdtModelInfo / RandomForestModelInfo feature importance output)."""
    if m.importances is not None:
        t = _importance_table(m.feature_cols, m.importances)
        rows = {"item": np.asarray(
                    ["algo", "num_trees", "max_depth"]
                    + [f"importance[{f}]" for f in t.col("feature")], object),
                "value": np.asarray(
                    [m.algo, str(m.features.shape[0]), str(m.max_depth)]
                    + [f"{v:.6f}" for v in t.col("importance")], object)}
        return MTable(rows)
    return MTable({"item": np.asarray(["algo", "num_trees", "max_depth"], object),
                   "value": np.asarray([m.algo, str(m.features.shape[0]),
                                        str(m.max_depth)], object)})


def _importance_table(feature_cols, imp) -> MTable:
    """Gain-based feature importances, normalized to sum 1 (reference
    TreeModelInfo feature importance)."""
    imp = np.asarray(imp, np.float64)
    tot = imp.sum()
    names = (list(feature_cols) if feature_cols
             else [f"f{i}" for i in range(len(imp))])
    return MTable({"feature": np.asarray(names, object),
                   "importance": imp / (tot if tot > 0 else 1.0)})


def _tree_params(op) -> TreeTrainParams:
    return TreeTrainParams(
        num_trees=op.get_num_trees(), max_depth=op.get_max_depth(),
        n_bins=op.get_max_bins(), learning_rate=op.get_learning_rate(),
        min_samples_leaf=op.get_min_samples_per_leaf(),
        reg_lambda=op.get_reg_lambda(),
        subsample_ratio=op.get_subsampling_ratio(),
        feature_subsample_ratio=op.get_feature_subsampling_ratio(),
        seed=op.get_seed())


class GbdtTrainBatchOp(BatchOperator, _TreeTrainParamsMixin):
    """reference: batch/classification/GbdtTrainBatchOp.java (binary)."""
    IS_REGRESSION = False

    def link_from(self, in_op: BatchOperator):
        with trace_span("gbdt.fit", cat="gbdt", coarse=True) as fit:
            t = in_op.get_output_table()
            with trace_span("gbdt.extract", cat="gbdt", coarse=True):
                (X, y, w, labels, fc, vc, lt, cat_mask, cat_cols,
                 vocabs) = _extract_xy(t=t, op=self,
                                       regression=self.IS_REGRESSION)
            if not self.IS_REGRESSION and len(labels) != 2:
                raise ValueError(
                    f"GBDT classifier is binary; got labels {labels}")
            p = _tree_params(self)
            info: dict = {}
            tf, tb, tm, tv, edges, base, curve, imp = gbdt_train(
                X, y, p, self.IS_REGRESSION, sample_weight=w,
                cat_mask=cat_mask, info=info)
            with trace_span("gbdt.model", cat="gbdt", coarse=True):
                tf, tb, tv = np.asarray(tf), np.asarray(tb), np.asarray(tv)
                thr = np.stack([bins_to_thresholds(tf[i], tb[i], edges)
                                for i in range(p.num_trees)])
                model = TreeModelData(
                    "gbdt", self.IS_REGRESSION, p.max_depth, tf, thr, tv,
                    base, p.learning_rate, labels, fc, vc, lt,
                    split_masks=np.asarray(tm), cat_cols=cat_cols,
                    cat_vocabs=vocabs, importances=np.asarray(imp))
                self._output = TreeModelDataConverter().save_model(model)
                self._side_outputs = [
                    MTable({"tree": np.arange(1, len(curve) + 1),
                            "loss": curve.astype(np.float64)}),
                    _importance_table(fc, imp)]
            info.update(features=tf, split_bins=tb, leaf_values=tv,
                        loss_curve=np.asarray(curve), base_score=base)
            self._train_info = info
            fit.set(rows=int(t.num_rows), trees=int(p.num_trees))
        if metrics_enabled():
            get_registry().inc("alink_gbdt_fits_total", 1)
        return self

    def get_train_info(self) -> dict:
        """What the last fit went through: which histogram ran
        (``hist``: ``"onehot"`` on the MXU or ``"scatter"``), how many
        node histograms a tree's block loops built and how many it took
        as ``parent - built`` (``hist_nodes``: ``{"built": 32, "derived":
        31}`` at depth 6; the counter ``alink_gbdt_hist_nodes_total{how=}``
        sums them over the trees, and the ``gbdt.grow`` span carries
        ``sibling: "subtract"`` beside ``hist``), the bin edges, the trees
        as the device grew them (``features``, ``split_bins``,
        ``leaf_values``, every node's ``counts``), the loss curve and the
        rows counted."""
        return self._train_info

    def get_model_info(self) -> MTable:
        m = TreeModelDataConverter().load_model(self.get_output_table())
        return _model_info_table(m)


class GbdtRegTrainBatchOp(GbdtTrainBatchOp):
    """reference: batch/regression/GbdtRegTrainBatchOp.java"""
    IS_REGRESSION = True


class RandomForestTrainBatchOp(BatchOperator, _TreeTrainParamsMixin):
    """reference: batch/classification/RandomForestTrainBatchOp.java"""
    IS_REGRESSION = False
    NUM_TREES = ParamInfo("num_trees", int, default=10,
                          validator=RangeValidator(1, None))
    SUBSAMPLING_RATIO = ParamInfo("subsampling_ratio", float, default=0.8)
    FEATURE_SUBSAMPLING_RATIO = ParamInfo("feature_subsampling_ratio", float,
                                          default=0.7)
    # True ensemble parallelism (whole trees per worker, reference
    # SeriesTrainFunction); None = auto (on for multi-tree forests)
    ENSEMBLE_PARALLEL = ParamInfo("ensemble_parallel", bool, default=None)

    def link_from(self, in_op: BatchOperator):
        t = in_op.get_output_table()
        (X, y, w, labels, fc, vc, lt, cat_mask, cat_cols,
         vocabs) = _extract_xy(t=t, op=self, regression=self.IS_REGRESSION)
        # the forests still grow on host rows (hist.build_tree)
        if isinstance(X, DenseBlockColumn):
            X = X.to_rows().astype(np.float64)
        y = y.to_values() if isinstance(y, RowBlockColumn) else np.asarray(y)
        w = (np.ones(len(y)) if w is None else
             w.to_values() if isinstance(w, RowBlockColumn) else w)
        p = _tree_params(self)
        if self.IS_REGRESSION:
            stats = np.stack([y * w, y * y * w, w], axis=1)
            kind = "variance"
        else:
            k = len(labels)
            onehot = np.eye(k)[y.astype(int)] * w[:, None]
            stats = np.concatenate([onehot, w[:, None]], axis=1)
            kind = "gini"
        tf, tb, tm, tv, edges, imp = forest_train(
            X, stats, p, kind, cat_mask=cat_mask,
            ensemble=self.params._m.get("ensemble_parallel"))
        thr = np.stack([bins_to_thresholds(np.asarray(tf[i]), np.asarray(tb[i]),
                                           edges) for i in range(p.num_trees)])
        model = TreeModelData(
            "rf", self.IS_REGRESSION, p.max_depth, np.asarray(tf), thr,
            np.asarray(tv), 0.0, 1.0, labels, fc, vc, lt,
            split_masks=np.asarray(tm), cat_cols=cat_cols, cat_vocabs=vocabs,
            importances=np.asarray(imp))
        self._output = TreeModelDataConverter().save_model(model)
        self._side_outputs = [_importance_table(fc, imp)]
        return self


    def get_model_info(self) -> MTable:
        m = TreeModelDataConverter().load_model(self.get_output_table())
        return _model_info_table(m)


class RandomForestRegTrainBatchOp(RandomForestTrainBatchOp):
    IS_REGRESSION = True


class DecisionTreeTrainBatchOp(RandomForestTrainBatchOp):
    """reference: batch/classification/DecisionTreeTrainBatchOp.java"""
    NUM_TREES = ParamInfo("num_trees", int, default=1,
                          validator=RangeValidator(1, 1))
    SUBSAMPLING_RATIO = ParamInfo("subsampling_ratio", float, default=1.0)
    FEATURE_SUBSAMPLING_RATIO = ParamInfo("feature_subsampling_ratio", float,
                                          default=1.0)


class DecisionTreeRegTrainBatchOp(DecisionTreeTrainBatchOp):
    IS_REGRESSION = True


class TreeModelMapper(ModelMapper):
    """Host-side batched forest traversal (reference common/tree/predictors/)."""

    def __init__(self, model_schema, data_schema, params=None, **kwargs):
        super().__init__(model_schema, data_schema, params, **kwargs)
        self.model: Optional[TreeModelData] = None

    def load_model(self, model_table: MTable):
        self.model = TreeModelDataConverter().load_model(model_table)

    def get_output_schema(self) -> TableSchema:
        """Output schema without running the mapper — what the stream
        predict twins (`ModelMapStreamOp._open`) need; the batch path
        derives it from `map_table`'s result and never noticed this was
        missing, which kept every tree stream twin from opening."""
        m = self.model
        return self._pred_output_schema(
            m.label_type if m else AlinkTypes.STRING,
            bool(m is not None and m.is_regression))

    def _model_width(self) -> int:
        """The feature width the model's splits can address: column
        count for feature_cols models, max split feature index + 1 for
        vector models (the model stores no vector size). Encoding to at
        least this width makes a batch's width independent of which
        sparse vectors happen to be in it — absent vector entries read
        as 0 instead of clamping the split's gather to a WRONG column
        (device) or raising (host numpy)."""
        m = self.model
        if m.feature_cols:
            return len(m.feature_cols)
        return int(max(int(m.features.max()), 0)) + 1

    def _encode_matrix(self, data: MTable, dtype=np.float64) -> np.ndarray:
        """Request table -> raw feature-value matrix (categorical columns
        ordinal-coded via the model vocabularies, OOV -> -1 which every
        traversal routes right), always :meth:`_model_width` columns
        wide. Shared by the host ``map_table`` path and the serving
        kernel's encode so the two cannot diverge."""
        m = self.model
        if m.cat_cols:
            n = data.num_rows
            X = np.empty((n, len(m.feature_cols)), dtype)
            for j, c in enumerate(m.feature_cols):
                col = data.col(c)
                if c in m.cat_vocabs:
                    lut = {v: i for i, v in enumerate(m.cat_vocabs[c])}
                    X[:, j] = [lut.get(str(v), -1) for v in col]  # OOV -> right
                else:
                    X[:, j] = np.asarray(col, np.float64)
            return X
        width = self._model_width()
        design = extract_design(data, m.feature_cols, m.vector_col,
                                np.float64,
                                vector_size=width if m.vector_col else None)
        X = design["X"] if design["kind"] == "dense" else None
        if X is None:
            from ....common.vector import SparseBatch
            X = SparseBatch(design["idx"], design["val"],
                            design["dim"]).to_dense(np.float64)
        if X.shape[1] < width:          # batch narrower than the splits
            X = np.concatenate(
                [X, np.zeros((X.shape[0], width - X.shape[1]), X.dtype)],
                axis=1)
        return np.asarray(X, dtype)

    def _cat_mask(self) -> Optional[np.ndarray]:
        m = self.model
        return (np.asarray([c in set(m.cat_cols) for c in
                            (m.feature_cols or [])], bool)
                if m.cat_cols else None)

    def map_table(self, data: MTable) -> MTable:
        m = self.model
        X = self._encode_matrix(data)
        T = m.features.shape[0]
        n = X.shape[0]
        cat_mask = self._cat_mask()

        def apply(t):
            return tree_apply_values(
                X, m.features[t], m.thresholds[t], m.max_depth,
                cat_mask=cat_mask,
                split_masks=(m.split_masks[t]
                             if m.split_masks is not None else None))

        if m.algo == "gbdt":
            score = np.full(n, m.base_score)
            for t in range(T):
                score += m.learning_rate * m.leaf_values[t][apply(t)]
            if m.is_regression:
                return self._emit(data, score, None, None)
            p_pos = 1.0 / (1.0 + np.exp(-np.clip(score, -500, 500)))
            probs = np.stack([1 - p_pos, p_pos], axis=1)  # labels sorted asc
            return self._emit(data, None, probs, m.labels)
        # random forest / decision tree
        if m.is_regression:
            acc = np.zeros(n)
            for t in range(T):
                acc += m.leaf_values[t][apply(t)]
            return self._emit(data, acc / T, None, None)
        k = m.leaf_values.shape[2]
        probs = np.zeros((n, k))
        for t in range(T):
            probs += m.leaf_values[t][apply(t)]
        probs /= np.maximum(probs.sum(1, keepdims=True), 1e-12)
        return self._emit(data, None, probs, m.labels)

    def serving_kernel(self):
        """Compiled-serving contract (serving/predictor.py) for the tree
        family — the gathered leaf-index traversal: every level of every
        tree is ONE batched gather of (feature, threshold[, split-mask])
        at the current node frontier, ``node -> 2*node + go_right``, and
        after ``max_depth`` levels the leaf values gather per tree and
        accumulate in the HOST mapper's exact order (a ``lax.scan`` over
        the tree axis whose xs are the already-rounded per-tree terms —
        serving/sharded.py ``scan_sum``). On the f64 test mesh the device
        scores are therefore bitwise-identical to the numpy traversal,
        so labels AND detail strings match the host mapper exactly; the
        per-row integer traversal makes bucket padding a bitwise no-op.
        The kernel signature carries tree GEOMETRY only (T, depth, node
        count, leaf arity, feature count) — weights (thresholds, leaf
        values, base score) are program arguments, so hot-swapped
        same-shaped forests reuse every compiled program."""
        m = self.model
        if m is None:
            raise RuntimeError(
                "load_model must be called before serving_kernel")
        import jax

        from ....serving.predictor import ServingKernel
        ship_dt = np.float64 if jax.config.jax_enable_x64 else np.float32
        T, nodes = m.features.shape
        depth = int(m.max_depth)
        n_class = (int(m.leaf_values.shape[2])
                   if m.leaf_values.ndim == 3 else 0)
        cat_mask = self._cat_mask()
        has_masks = m.split_masks is not None and cat_mask is not None
        n_bins = int(m.split_masks.shape[2]) if has_masks else 0
        n_feat = int(len(m.feature_cols)) if m.feature_cols else None
        gbdt = m.algo == "gbdt"

        # thresholds rounded DOWN into the shipping dtype: a row that ties
        # a cut point goes the host mapper's way in float32 too
        model_arrays = [np.asarray(m.features, np.int32),
                        thresholds_as(m.thresholds, ship_dt),
                        np.asarray(m.leaf_values, ship_dt),
                        np.asarray(m.base_score, ship_dt),
                        np.asarray(m.learning_rate, ship_dt)]
        if has_masks:
            model_arrays.append(np.asarray(m.split_masks, bool))
            model_arrays.append(np.asarray(cat_mask, bool))
        model_arrays = tuple(model_arrays)
        signature = ("tree", m.algo, bool(m.is_regression), T, depth,
                     nodes, n_class, n_feat, has_masks, n_bins,
                     str(ship_dt.__name__))

        def encode(data: MTable, bucket: int):
            Xf = self._encode_matrix(data, ship_dt)
            X = np.zeros((bucket, Xf.shape[1]), ship_dt)
            X[:data.num_rows] = Xf
            return ("dense", (X,))

        def _apply_all(mdl, X):
            """(n, T) leaf indices — the vectorized device twin of the
            host ``tree_apply_values`` descent."""
            import jax.numpy as jnp
            features, thresholds = mdl[0], mdl[1]
            n = X.shape[0]
            tr = jnp.arange(T)[None, :]
            rows = jnp.arange(n)[:, None]
            node = jnp.zeros((n, T), jnp.int32)
            offset = 0
            for _level in range(depth):
                gi = offset + node
                f = features[tr, gi]
                thr = thresholds[tr, gi]
                x = X[rows, jnp.maximum(f, 0)]
                go_right = (f >= 0) & (x > thr)
                if has_masks:
                    masks, catm = mdl[5], mdl[6]
                    code = jnp.round(x).astype(jnp.int32)
                    in_left = jnp.where(
                        code >= 0,
                        masks[tr, gi, jnp.clip(code, 0, n_bins - 1)],
                        False)
                    is_cat = catm[jnp.maximum(f, 0)] & (f >= 0)
                    go_right = jnp.where(is_cat, (f >= 0) & ~in_left,
                                         go_right)
                node = node * 2 + go_right
                offset += 1 << _level
            return node, tr

        def _score(mdl, X):
            from ....serving.sharded import scan_sum
            leafs, base, lr = mdl[2], mdl[3], mdl[4]
            node, tr = _apply_all(mdl, X)
            if gbdt:
                # host order: score = full(base); score += lr*leaf[t]
                # per tree, left to right — the scan carry starts at
                # base and adds the rounded lr*leaf terms, reproducing
                # the numpy loop bitwise
                return _gbdt_acc(base, lr * leafs[tr, node])
            # rf/dt: per-tree leaf stats sum over the tree axis — (n,)
            # regression / (n, k) classification; decode normalizes
            return scan_sum(leafs[tr, node], axis=1)

        def _gbdt_acc(base, terms):
            """base + terms[0] + terms[1] + ... in the host loop's exact
            association: the scan carry STARTS at base."""
            import jax
            import jax.numpy as jnp
            t = jnp.moveaxis(terms, 1, 0)
            acc0 = jnp.broadcast_to(base, (terms.shape[0],)).astype(
                terms.dtype)

            def body(acc, x):
                return acc + x, None

            acc, _ = jax.lax.scan(body, acc0, t)
            return acc

        def decode(outputs, data: MTable) -> MTable:
            out = np.asarray(outputs[0], np.float64)
            if gbdt:
                if m.is_regression:
                    return self._emit(data, out, None, None)
                p_pos = 1.0 / (1.0 + np.exp(-np.clip(out, -500, 500)))
                probs = np.stack([1 - p_pos, p_pos], axis=1)
                return self._emit(data, None, probs, m.labels)
            if m.is_regression:
                return self._emit(data, out / T, None, None)
            probs = out / np.maximum(out.sum(1, keepdims=True), 1e-12)
            return self._emit(data, None, probs, m.labels)

        return ServingKernel(signature=signature,
                             model_arrays=model_arrays,
                             encode=encode, device_fns={"dense": _score},
                             decode=decode)

    def _emit(self, data, scores, probs, labels):
        m = self.model
        pred_col = self.params._m.get("prediction_col", "pred")
        detail_col = self.params._m.get("prediction_detail_col")
        reserved = self.params._m.get("reserved_cols")
        if probs is None:
            helper = OutputColsHelper(data.schema, [pred_col],
                                      [AlinkTypes.DOUBLE], reserved)
            return helper.build_output(data, [scores])
        pick = probs.argmax(1)
        preds = np.empty(len(pick), object)
        preds[:] = [labels[i] for i in pick]
        cols, types, vals = [pred_col], [m.label_type], [preds]
        if detail_col:
            details = np.asarray(
                [json.dumps({str(l): float(p) for l, p in zip(labels, row)})
                 for row in probs], object)
            cols.append(detail_col)
            types.append(AlinkTypes.STRING)
            vals.append(details)
        helper = OutputColsHelper(data.schema, cols, types, reserved)
        return helper.build_output(data, vals)


class _TreePredictBase(ModelMapBatchOp, HasPredictionCol, HasPredictionDetailCol,
                       HasReservedCols):
    MAPPER_CLS = TreeModelMapper


class GbdtPredictBatchOp(_TreePredictBase):
    pass


class GbdtRegPredictBatchOp(_TreePredictBase):
    pass


class RandomForestPredictBatchOp(_TreePredictBase):
    pass


class RandomForestRegPredictBatchOp(_TreePredictBase):
    pass


class DecisionTreePredictBatchOp(_TreePredictBase):
    pass


class DecisionTreeRegPredictBatchOp(_TreePredictBase):
    pass
