"""Linear-model training core.

Re-design of ``BaseLinearModelTrainBatchOp``
(common/linear/BaseLinearModelTrainBatchOp.java:68-104 linkFrom flow:
label encode -> Tuple3(weight,label,vec) transform -> stats/standardization
(:111-180) -> ``optimize()`` dispatch (:229-265) -> model rows via
LinearModelDataConverter :91-102) plus the model value object
(common/linear/LinearModelData.java).

Differences by design (TPU-first, not a port):
  * features cross to the device once as dense blocks / padded-COO batches;
  * standardization statistics come from one weighted-moment pass
    (psum-able) instead of the VectorSummarizer dataflow;
  * the intercept is excluded from L1/L2 regularization;
  * sparse input is scaled but not centered (keeps sparsity), like the
    reference.

A DENSE table trains where it lies: ``extract_design`` hands a
``DenseBlockColumn`` through (host rows are packed into one), its moments
are ONE blocked pass on the device (``jit_linear_moments``), and
standardization and the intercept are folded into the coefficients inside
the step program (``optim/objfunc.py``: ``scale = 1 / std``, ``shift =
mean / std``), so the table is neither rewritten nor copied: a table of
one byte a value stays one byte a value. Labels and weights ride beside
it as ``(blocks, S, 128)`` columns.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ....common.columnar import (DenseBlockColumn, RowBlockColumn,
                                  as_block_column, block_values,
                                  block_weights)
from ....common.metrics import get_registry, metrics_enabled
from ....common.mlenv import MLEnvironmentFactory
from ....common.mtable import MTable
from ....common.tracing import trace_span
from ....engine import IterativeComQueue
from ....engine.communication import manifest_all_gather
from ....common.params import Params
from ....common.types import AlinkTypes, TableSchema
from ....model.converters import (LabeledModelDataConverter, decode_array,
                                  encode_array)
from ..blocked import block_at
from ..dataproc.feature_extract import add_intercept, extract_design
from ..optim.objfunc import (HingeLossFunc, HuberLossFunc, LogLossFunc,
                             PerceptronLossFunc, SmoothHingeLossFunc,
                             SoftmaxObjFunc, SquareLossFunc, SvrLossFunc,
                             UnaryLossObjFunc, walk_path)
from ..optim.optimizers import OptimParams, optimize


class LinearModelType:
    LR = "LR"
    SVM = "SVM"
    LinearReg = "LinearReg"
    SVR = "SVR"
    Perceptron = "Perceptron"
    Softmax = "Softmax"
    AFT = "AFT"

    LOSSES = {
        "LR": LogLossFunc, "SVM": HingeLossFunc, "LinearReg": SquareLossFunc,
        "SVR": SvrLossFunc, "Perceptron": PerceptronLossFunc,
    }
    IS_REGRESSION = {"LinearReg", "SVR"}


@dataclass
class LinearModelData:
    model_name: str
    linear_model_type: str
    has_intercept: bool
    vector_col: Optional[str]
    feature_names: Optional[List[str]]
    vector_size: int
    coef: np.ndarray                       # (dim,) or flattened (k-1, dim) for Softmax
    label_values: List[Any] = field(default_factory=list)
    label_type: str = AlinkTypes.STRING
    loss_curve: Optional[np.ndarray] = None


class LinearModelDataConverter(LabeledModelDataConverter):
    """Model rows (reference common/linear/LinearModelDataConverter.java)."""

    def __init__(self, label_type: str = AlinkTypes.STRING):
        super().__init__(label_type)

    @classmethod
    def load_table(cls, table) -> "LinearModelData":
        """Load a serialized linear model table, sniffing the label
        type from its third column (the labeled layout's label slot;
        STRING for the label-less two-column shape). The ONE
        label-type/positive-label convention every consumer of a
        linear model table must share — the FTRL warm start, the
        predict mapper, and the online DAG's eval leg all load
        through here (``label_values[0]`` is the positive label)."""
        label_type = table.schema.types[2] if len(table.schema) > 2 \
            else AlinkTypes.STRING
        return cls(label_type).load_model(table)

    def serialize_model(self, m: LinearModelData):
        meta = Params({
            "model_name": m.model_name, "linear_model_type": m.linear_model_type,
            "has_intercept": m.has_intercept, "vector_col": m.vector_col,
            "feature_names": m.feature_names, "vector_size": m.vector_size,
            "label_type": m.label_type,
        })
        return meta, [encode_array(m.coef)], list(m.label_values)

    def deserialize_model(self, meta: Params, data: List[str], labels: List[Any]):
        get = lambda k, d=None: meta._m.get(k, d)  # noqa: E731
        return LinearModelData(
            model_name=get("model_name", ""),
            linear_model_type=get("linear_model_type", "LR"),
            has_intercept=bool(get("has_intercept", True)),
            vector_col=get("vector_col"),
            feature_names=get("feature_names"),
            vector_size=int(get("vector_size", 0)),
            coef=decode_array(data[0]),
            label_values=labels,
            label_type=get("label_type", AlinkTypes.STRING),
        )


def _whole_number_blocks(raw_labels) -> bool:
    return isinstance(raw_labels, RowBlockColumn) \
        and np.issubdtype(raw_labels.dtype, np.integer)


def _label_range(col: RowBlockColumn) -> Tuple[int, int, int]:
    """``(least, largest, rows that hold neither)`` of a block column of
    whole numbers, reduced where it lies (three numbers are fetched)."""
    b = jnp.asarray(col.blocks) if col.on_device else col.blocks
    xp = jnp if col.on_device else np
    nb, S, L = b.shape
    at = (xp.arange(nb * S * L, dtype=xp.int32).reshape(nb, S, L)
          < col.n_rows)
    lo = int(xp.min(xp.where(at, b, xp.iinfo(b.dtype).max)))
    hi = int(xp.max(xp.where(at, b, xp.iinfo(b.dtype).min)))
    other = int(xp.sum(at & (b != lo) & (b != hi)))
    return lo, hi, other


def encode_labels(raw_labels: np.ndarray, positive_value=None) -> Tuple[List[Any], np.ndarray]:
    """Distinct labels + per-row {-1,+1} targets (binary).

    reference: getLabelInfo/getLabelValues (BaseLinearModelTrainBatchOp.java).
    Ordering: positive label first; default positive = largest distinct
    (so numeric {0,1} gets positive=1). A block column of whole numbers
    is encoded where it lies: its two values from a reduction, the
    targets ``(blocks, S, 128)`` beside it, no row on the host.
    """
    if _whole_number_blocks(raw_labels):
        lo, hi, other = _label_range(raw_labels)
        if other or lo == hi:
            raise ValueError("binary trainer needs exactly 2 label values")
        distinct = _positive_first([hi, lo], positive_value)
        # rows past n_rows read -1: their weight is 0
        return distinct, (raw_labels.blocks == distinct[0]) * 2.0 - 1.0
    distinct = sorted(set(_canon(v) for v in raw_labels), key=_sort_key, reverse=True)
    if len(distinct) != 2:
        raise ValueError(f"binary trainer needs exactly 2 label values, got {distinct}")
    distinct = _positive_first(distinct, positive_value)
    y = np.where([_canon(v) == distinct[0] for v in raw_labels], 1.0, -1.0)
    return distinct, y


def _positive_first(distinct: List[Any], positive_value) -> List[Any]:
    """``distinct`` with the label that reads as ``positive_value`` first
    (left as it is where none is asked for)."""
    if positive_value is None:
        return distinct
    pv = _canon(positive_value)
    match = [l for l in distinct if str(l) == str(pv)]
    if not match:
        raise ValueError(f"positive label {positive_value!r} not in {distinct}")
    return [match[0]] + [l for l in distinct if l is not match[0]]


def index_labels(raw_labels: np.ndarray) -> Tuple[List[Any], np.ndarray]:
    """Distinct labels + integer class ids (multiclass, reference Softmax).
    A block column of whole numbers ``0 .. k - 1`` is its own index: the
    labels are ``0 .. max`` (a reduction where the column lies; a class no
    row holds keeps its slot), the ids are the column's blocks. The host
    form gives the same ``label_values`` order for such labels."""
    if _whole_number_blocks(raw_labels):
        lo, hi, _ = _label_range(raw_labels)
        if lo < 0:
            raise ValueError("a block column of class ids starts at 0")
        return list(range(hi + 1)), raw_labels.blocks
    distinct = sorted(set(_canon(v) for v in raw_labels), key=_sort_key)
    lookup = {l: i for i, l in enumerate(distinct)}
    y = np.asarray([lookup[_canon(v)] for v in raw_labels], np.float64)
    return distinct, y


def _canon(v):
    if isinstance(v, (np.generic,)):
        return v.item()
    return v


def _sort_key(v):
    return (0, float(v)) if isinstance(v, (int, float, bool)) else (1, str(v))


@dataclass
class LinearTrainPrep:
    """The hyperparameter-independent half of the linear train flow.

    Everything up to (and excluding) ``optimize()`` — design extraction,
    label encoding, standardization moments, field-block detection —
    depends only on the data and the structural params, never on the
    carry-resident tuning axes (``l1``/``l2``/``learning_rate``/
    ``epsilon``). The mesh-parallel tuning sweep (``alink_tpu/tuning/``)
    therefore prepares ONCE per split and sweeps N points through
    :meth:`objective` + one batched program, finishing each point with
    :meth:`finish` — the exact de-augment/de-standardize/model-build
    tail the serial path runs."""
    env: Any
    dtype: Any
    model_type: str
    softmax: bool
    regression: bool
    labels: List[Any]
    label_type: str
    train: Dict[str, np.ndarray]
    dim: int
    feat_dim: int
    mean: np.ndarray
    std: np.ndarray
    standardize: bool
    with_intercept: bool
    fb_meta: Any                    # augmented FieldBlockMeta, or None
    reg_free: int
    vector_col: Optional[str]
    feature_cols: Optional[List[str]]
    loss_kwargs: Dict[str, Any]
    rows: int = 0
    moments_rows: Optional[int] = None    # rows the moments pass counted

    def objective(self, l1: float, l2: float):
        """The training objective at (l1, l2) — the serial path's obj
        construction, verbatim."""
        if self.softmax:
            k = len(self.labels)
            return SoftmaxObjFunc(k, self.dim, l1=l1, l2=l2,
                                  reg_free_cols=self.reg_free)
        loss_cls = LinearModelType.LOSSES[self.model_type]
        return UnaryLossObjFunc(loss_cls(**self.loss_kwargs), self.dim,
                                l1=l1, l2=l2, reg_free_head=self.reg_free,
                                fb_meta=self.fb_meta)

    def finish(self, coef, loss_curve) -> Tuple[MTable, MTable]:
        """Fitted coefficients -> (model_table, train_info): fb
        intercept de-augmentation, de-standardization, model rows."""
        coef = np.asarray(coef)
        if self.fb_meta is not None and self.with_intercept:
            # de-augment: [intercept slot, dead slots..., features]
            coef = np.concatenate([coef[:1],
                                   coef[self.fb_meta.field_size:]])
        if self.standardize:
            coef = _destandardize_coef(coef, self.mean, self.std,
                                       self.with_intercept, self.softmax,
                                       len(self.labels))
        model = LinearModelData(
            model_name=f"{self.model_type} model",
            linear_model_type=self.model_type,
            has_intercept=bool(self.with_intercept),
            vector_col=self.vector_col,
            feature_names=self.feature_cols if not self.vector_col else None,
            vector_size=int(self.feat_dim),
            coef=np.asarray(coef, np.float64), label_values=self.labels,
            label_type=self.label_type, loss_curve=loss_curve)
        model_table = LinearModelDataConverter(
            self.label_type).save_model(model)
        info = MTable({"iter": np.arange(1, len(loss_curve) + 1),
                       "loss": np.asarray(loss_curve, np.float64)})
        return model_table, info


def prepare_linear_train(data: MTable, op, model_type: str
                         ) -> LinearTrainPrep:
    """The shared front half of :func:`train_linear_model` (see
    :class:`LinearTrainPrep`)."""
    env = MLEnvironmentFactory.get(op.get_ml_environment_id())
    feature_cols = op.params._m.get("feature_cols")
    vector_col = op.params._m.get("vector_col")
    label_col = op.params._m.get("label_col")
    weight_col = op.params._m.get("weight_col")
    with_intercept = op.params._m.get("with_intercept", True)
    standardize = op.params._m.get("standardization", True)
    l1 = float(op.params._m.get("l1", 0.0) or 0.0)
    l2 = float(op.params._m.get("l2", 0.0) or 0.0)
    dtype = np.float64 if _x64_enabled() else np.float32

    if not vector_col:
        from ..dataproc.feature_extract import resolve_feature_cols
        feature_cols = resolve_feature_cols(data, feature_cols, label_col,
                                            exclude=[weight_col] if weight_col else [])
    with trace_span("linear.extract", cat="linear", coarse=True):
        design = extract_design(data, feature_cols, vector_col, dtype)
    n = data.num_rows
    dense = design["kind"] == "dense"
    if dense:
        # the one dense form: the table as blocks (a block column is used
        # where it lies), the weights beside it (made where the table
        # lives; the padding's are 0)
        col = as_block_column(design["X"], env.num_workers)
        wcol = data.col(weight_col) if weight_col else None
        w = block_weights(col, getattr(wcol, "blocks", wcol), dtype)
    else:
        w = (np.asarray(data.col(weight_col), dtype) if weight_col
             else np.ones(n, dtype))

    # -- label encoding --------------------------------------------------
    softmax = model_type == LinearModelType.Softmax
    regression = model_type in LinearModelType.IS_REGRESSION
    raw = data.col(label_col)
    label_type = data.schema.type_of(label_col)
    if regression:
        labels, y = [], (raw.blocks if isinstance(raw, RowBlockColumn)
                         else np.asarray(raw, dtype))
    elif softmax:
        labels, y = index_labels(raw)
    else:
        labels, y = encode_labels(raw, op.params._m.get("positive_label_value_string"))

    # -- standardization (reference :111-180) ----------------------------
    moments_rows = None
    if dense:
        mean, std, moments_rows = linear_moments(col, w, env)
    else:
        mean, std = _weighted_moments(design, w)
        mean = np.zeros_like(mean)  # sparse path scales only; no centering

    # field-blocked fast path (ops/fieldblock.py): field-aware-hashed input
    # trains through factored-one-hot MXU kernels instead of random
    # gather/scatter. The intercept becomes a prepended constant field
    # (local index 0) so fields stay uniform; its unused slots get no
    # gradient and stay 0.
    fb = None
    if design["kind"] == "sparse" and not softmax:
        from ....ops.fieldblock import detect_fieldblock
        fb = detect_fieldblock(design["idx"], design["val"], design["dim"])
    feat_dim = design["dim"]  # pre-intercept feature dim (model vector_size)
    if fb is not None:
        fb_idx, fb_val, meta = fb
        if standardize:
            from ....ops.fieldblock import fb_to_flat_indices
            scale = (1.0 / std).astype(dtype)
            flat = fb_to_flat_indices(fb_idx, meta)
            fb_val = (scale[flat] if fb_val is None else
                      fb_val.astype(dtype) * scale[flat])
        if with_intercept:
            from ....ops.fieldblock import FieldBlockMeta
            fb_idx = np.concatenate(
                [np.zeros((n, 1), fb_idx.dtype), fb_idx], axis=1)
            if fb_val is not None:
                fb_val = np.concatenate(
                    [np.ones((n, 1), fb_val.dtype), fb_val], axis=1)
            meta = FieldBlockMeta(meta.num_fields + 1, meta.field_size)
        dim = meta.dim
    elif dense:
        # standardization and the intercept are folded into the
        # coefficients by the passes: the table stays as it is
        dim = design["dim"] + int(bool(with_intercept))
    else:
        if standardize:
            design = _apply_standardization(design, mean, std)
        if with_intercept:
            design = add_intercept(design, dtype)
        dim = design["dim"]

    # the fb intercept field owns the first field_size slots, all reg-free
    reg_free = 0 if not with_intercept else \
        (meta.field_size if fb is not None else 1)
    loss_kwargs: Dict[str, Any] = {}
    if model_type == LinearModelType.SVR:
        loss_kwargs["epsilon"] = float(op.params._m.get("tau", 0.1))

    if fb is not None:
        train = {"fb_idx": fb_idx}
        if fb_val is not None:
            train["fb_val"] = fb_val
    elif dense:
        train = {"X": col}
        if standardize:
            train["scale"] = (1.0 / std).astype(dtype)
            train["shift"] = (mean / std).astype(dtype)
    else:
        train = {k2: v for k2, v in design.items() if k2 in ("idx", "val")}
    if dense:
        y = block_values(col, y)
        train["y"] = y if softmax else y.astype(dtype)
    else:
        train["y"] = np.asarray(y).astype(dtype)
    train["w"] = w
    return LinearTrainPrep(
        rows=int(n), moments_rows=moments_rows,
        env=env, dtype=dtype, model_type=model_type, softmax=softmax,
        regression=regression, labels=labels, label_type=label_type,
        train=train, dim=dim, feat_dim=int(feat_dim), mean=mean, std=std,
        standardize=bool(standardize), with_intercept=bool(with_intercept),
        fb_meta=meta if fb is not None else None, reg_free=reg_free,
        vector_col=vector_col, feature_cols=feature_cols,
        loss_kwargs=loss_kwargs)


class LinearTrainInfo(MTable):
    """A linear fit's train info: the (iter, loss) rows every trainer
    gives as side output 0, which also answer BY KEY for what the fit
    went through (``info["coef_trace"]``, ``dict(info)``): the loss
    curve, the moments and the rows they were taken over, ``paths``
    naming what ran and, for a quasi-Newton fit of a dense table, per
    superstep the standardized-space coefficients and the averaged
    gradient it started from (``coef_trace``, ``grad_trace``), the chosen
    rung and step of the line search (``rung_trace``, ``step_trace``) and
    the rows the two passes counted on the device (``rows_trace``)."""

    def __init__(self, table: MTable, record: Dict[str, Any]):
        super().__init__({n: table.col(n) for n in table.col_names})
        self.record = dict(record)

    def keys(self):
        return self.record.keys()

    def get(self, key, default=None):
        return self.record.get(key, default)

    def __contains__(self, key):
        return key in self.record

    def __getitem__(self, key):
        if key in self.record:
            return self.record[key]
        return super().__getitem__(key)


def train_linear_model(data: MTable, op, model_type: str
                       ) -> Tuple[MTable, LinearTrainInfo]:
    """Full train flow; ``op`` supplies params. Returns (model_table,
    train_info)."""
    with trace_span("linear.fit", cat="linear", coarse=True) as fit:
        prep = prepare_linear_train(data, op, model_type)
        l1 = float(op.params._m.get("l1", 0.0) or 0.0)
        l2 = float(op.params._m.get("l2", 0.0) or 0.0)
        method = _default_method(op, l1)
        lr = op.params._m.get("learning_rate")
        if lr is None:
            lr = default_learning_rate(method)
        optim = OptimParams(
            method=method,
            max_iter=int(op.params._m.get("max_iter", 100)),
            epsilon=float(op.params._m.get("epsilon", 1e-6)),
            learning_rate=float(lr),
            mini_batch_fraction=float(op.params._m.get("mini_batch_fraction", 0.1)),
            seed=int(op.params._m.get("seed", 0) or 0),
        )
        obj = prep.objective(l1, l2)
        paths = fit_paths(prep)
        went: Dict[str, Any] = {}
        with trace_span("linear.optimize", cat="linear", coarse=True,
                        args={"method": method, "max_iter": optim.max_iter,
                              "classes": len(prep.labels), "dim": prep.dim,
                              "pass": paths["pass"],
                              "walk": paths.get("walk", "none")}):
            coef, loss_curve, steps = optimize(obj, prep.train, optim,
                                               prep.env, info=went)
        with trace_span("linear.model", cat="linear", coarse=True):
            model_table, curve = prep.finish(coef, loss_curve)
        fit.set(rows=prep.rows, steps=int(steps))
    _count_fit(prep, went, int(steps), paths.get("walk"))
    went.update(loss_curve=np.asarray(loss_curve), steps=int(steps),
                mean=prep.mean, std=prep.std, rows=prep.rows,
                moments_rows=prep.moments_rows, paths=paths, l2=l2,
                method=method, label_values=list(prep.labels))
    return model_table, LinearTrainInfo(curve, went)


def fit_paths(prep: LinearTrainPrep) -> Dict[str, str]:
    """What ran a fit, by name: the design's form, who took the moments,
    the passes' arithmetic (``blocked:bf16x3`` a table of bytes as exact
    bfloat16 against the coefficients split in three, ``blocked:highest``
    a table of floats at matmul precision highest) and what walked them
    (``walk``: ``kernel`` the multinomial passes over a table of bytes as
    one streamed Pallas kernel each, ``kernels/linear.py``; ``xla`` the
    block loop; read from the input by ``objfunc.walk_path``, here and
    again where the step program is traced)."""
    X = prep.train.get("X")
    if X is None:
        form = "fieldblock" if prep.fb_meta is not None else "sparse"
        return {"design": form, "moments": "host", "pass": form}
    byte = np.issubdtype(X.value_dtype, np.integer)
    walk = walk_path(X.blocks, len(prep.labels) - 1) if prep.softmax \
        else "xla"
    return {"design": f"blocks:{X.value_dtype.name}",
            "moments": MOMENTS_PROGRAM,
            "pass": "blocked:bf16x3" if byte else "blocked:highest",
            "walk": walk}


def _count_fit(prep: LinearTrainPrep, went: Dict, steps: int,
               walk: Optional[str]) -> None:
    """The fit's counters: rows each pass counted ON THE DEVICE (the
    moments pass and, a superstep, the gradient and the line-search
    pass), supersteps, passes by kind, the table blocks each kind of
    pass went over by ``walk``, fits."""
    if not metrics_enabled():
        return
    reg = get_registry()
    reg.inc("alink_linear_fits_total", 1)
    reg.inc("alink_linear_supersteps_total", steps)
    if prep.moments_rows is not None:
        reg.inc("alink_linear_rows_total", int(prep.moments_rows))
        reg.inc("alink_linear_passes_total", 1, {"pass": "moments"})
    if "rows_trace" in went:
        rows = np.asarray(went["rows_trace"], np.int64)
        reg.inc("alink_linear_rows_total", int(rows.sum()))
        blocks = len(rows) * int(prep.train["X"].blocks.shape[0])
        for kind in ("grad", "line"):
            reg.inc("alink_linear_passes_total", len(rows), {"pass": kind})
            reg.inc("alink_linear_pass_blocks_total", blocks,
                    {"pass": kind, "walk": walk})


#: the engine names the moments program ``jit_<first word of its key>``
MOMENTS_PROGRAM = "linear_moments"


def linear_moments(col: DenseBlockColumn, weights, env
                   ) -> Tuple[np.ndarray, np.ndarray, int]:
    """``(mean, std, rows)`` of a blocked table's columns under
    ``weights`` ``(blocks, S, 128)``, by ONE pass on the device
    (``jit_linear_moments``): a block's weighted mean and its squared
    deviations about THAT mean come from one read of the block, and the
    blocks (then the workers) are joined by the pairwise update of Chan,
    Golub and LeVeque, so nothing cancels as ``E x^2 - mean^2`` would; the
    rows of non-zero weight are counted as whole numbers. A column with
    ``std < 1e-12`` keeps ``std = 1``. Moments are float64 on the host,
    float32 sums on a device without float64."""
    d = col.dim
    with trace_span("linear.moments", cat="linear", coarse=True,
                    args={"rows": col.n_rows, "dim": d,
                          "dtype": col.value_dtype.name}):
        res = moments_queue(env, col.blocks, weights).exec()
        moments, rows = res.get_all(["moments", "rows"])
    moments = np.asarray(moments, np.float64)
    mean = moments[:d]
    std = np.sqrt(np.maximum(moments[d:2 * d], 0.0)
                  / max(moments[2 * d], 1e-12))
    return mean, np.where(std < 1e-12, 1.0, std), int(rows)


def moments_queue(env, blocks, weights) -> IterativeComQueue:
    """The moments program's queue over a table's ``blocks`` and its
    ``weights`` (arrays, or ``ShapeDtypeStruct``s to lower it from
    shapes)."""
    d = int(blocks.shape[1])
    dt = np.dtype(weights.dtype)
    tiny = np.finfo(dt).tiny

    def join(a, b):
        (na, ma, qa), (nb, mb, qb) = a, b
        tot = na + nb
        frac = nb / jnp.maximum(tot, tiny)
        delta = mb - ma
        return tot, ma + delta * frac, qa + qb + delta * delta * (na * frac)

    def stage(ctx):
        Xs, Ws = ctx.get_obj("X"), ctx.get_obj("w")

        def body(i, c):
            run, rows = c
            xb = block_at(Xs, i).astype(dt)
            wb = block_at(Ws, i)
            nb = wb.sum()
            mb = (xb * wb[None]).sum((1, 2)) / jnp.maximum(nb, tiny)
            qb = (wb[None] * (xb - mb[:, None, None]) ** 2).sum((1, 2))
            return join(run, (nb, mb, qb)), \
                rows + (wb != 0).sum(dtype=jnp.int32)

        zero = jnp.zeros((d,), dt)
        with jax.named_scope("linear_moments"):
            (n, mean, m2), rows = jax.lax.fori_loop(
                0, Xs.shape[0], body,
                ((jnp.asarray(0, dt), zero, zero), jnp.asarray(0, jnp.int32)))
        mine = jnp.concatenate([mean, m2, n[None]])
        every = manifest_all_gather(mine, ctx.AXIS, name="linear_moments",
                                    num_workers=ctx.num_task)
        every = every.reshape(ctx.num_task, 2 * d + 1)
        run = (every[0, 2 * d], every[0, :d], every[0, d:2 * d])
        for t in range(1, ctx.num_task):
            run = join(run, (every[t, 2 * d], every[t, :d],
                             every[t, d:2 * d]))
        ctx.put_obj("moments", jnp.concatenate([run[1], run[2], run[0][None]]))
        ctx.put_obj("rows", ctx.all_reduce_sum(rows))

    return (IterativeComQueue(env=env, max_iter=1)
            .init_with_partitioned_data("X", blocks)
            .init_with_partitioned_data("w", weights)
            .add(stage)
            .set_program_key((MOMENTS_PROGRAM, d, str(dt),
                              str(np.dtype(blocks.dtype)))))


def _x64_enabled() -> bool:
    return bool(jax.config.jax_enable_x64)


def _default_method(op, l1: float) -> str:
    """The ONE method-resolution rule (explicit ``optim_method`` wins;
    otherwise OWLQN iff l1 > 0). ``op`` is anything carrying the linear
    train params (a train op or a pipeline estimator) — the tuning
    sweep's per-point resolution reuses this exact function so the
    flag-on candidate set can never drift from the serial loop's."""
    m = op.params._m.get("optim_method")
    if m:
        return str(m)
    return "OWLQN" if l1 > 0 else "LBFGS"


def default_learning_rate(method: str) -> float:
    """The serial default when no ``learning_rate`` param is set:
    line-search base for the (quasi-)Newton methods; step size for SGD.
    Shared with the tuning sweep's per-point resolution."""
    return 0.1 if method.upper() == "SGD" else 1.0


def _weighted_moments(design: Dict, w: np.ndarray):
    """Moments of a SPARSE design on the host (a dense table's are
    :func:`linear_moments`')."""
    W = max(float(w.sum()), 1e-12)
    dim = design["dim"]
    idx, val = design["idx"], design["val"]
    mean = np.zeros(dim, val.dtype)
    sq = np.zeros(dim, val.dtype)
    np.add.at(mean, idx.reshape(-1), (val * w[:, None]).reshape(-1))
    np.add.at(sq, idx.reshape(-1), (val ** 2 * w[:, None]).reshape(-1))
    mean /= W
    var = sq / W - mean ** 2  # zeros count toward the moments
    std = np.sqrt(np.maximum(var, 0.0))
    std = np.where(std < 1e-12, 1.0, std)
    return mean, std


def _apply_standardization(design: Dict, mean, std):
    # sparse: scale only, centering would densify
    val = design["val"] / std[design["idx"]]
    return {"kind": "sparse", "idx": design["idx"], "val": val, "dim": design["dim"]}


def _destandardize_coef(coef, mean, std, with_intercept, softmax, k):
    if softmax:
        W = coef.reshape(k - 1, -1)
        if with_intercept:
            b, Wf = W[:, 0], W[:, 1:]
            Wo = Wf / std
            bo = b - (Wf * (mean / std)).sum(1)
            return np.concatenate([bo[:, None], Wo], 1).reshape(-1)
        return (W / std).reshape(-1)
    if with_intercept:
        b, wf = coef[0], coef[1:]
        wo = wf / std
        bo = b - float((wf * (mean / std)).sum())
        return np.concatenate([[bo], wo])
    return coef / std
