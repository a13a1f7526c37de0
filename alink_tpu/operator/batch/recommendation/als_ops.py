"""ALS batch operators.

Re-design of batch/recommendation/ AlsTrainBatchOp, AlsPredictBatchOp,
AlsTopKPredictBatchOp + AlsModelDataConverter (common/recommendation/).
"""

from __future__ import annotations

import json
from typing import List, Optional

import numpy as np

from ....common.columnar import RowBlockColumn
from ....common.metrics import get_registry, metrics_enabled
from ....common.mtable import MTable
from ....common.params import ParamInfo, Params, RangeValidator
from ....common.tracing import trace_span
from ....common.types import AlinkTypes, TableSchema
from ....model.converters import (ArrayPayload, SimpleModelDataConverter,
                                  decode_array, payload_array)
from ....params.shared import HasPredictionCol, HasReservedCols, HasSeed
from ...base import BatchOperator
from ...common.recommendation.als import AlsTrainParams, als_train


class AlsModelData:
    """``user_ids`` / ``item_ids``: the id of each factor row, a whole-number
    ``ndarray`` or a list of other values; the factors ``(rows, rank)`` are
    host or device-resident arrays."""

    def __init__(self, user_ids, item_ids, user_factors, item_factors,
                 user_col: str, item_col: str, rate_col: str):
        self.user_ids = user_ids
        self.item_ids = item_ids
        self.user_factors = user_factors
        self.item_factors = item_factors
        self.user_col, self.item_col, self.rate_col = user_col, item_col, rate_col


def _ids_payload(ids):
    """Whole-number ids travel as an array; any other ids (a small table's
    strings) as the JSON list they always were."""
    if isinstance(ids, np.ndarray) and ids.dtype.kind in "iu":
        return ArrayPayload(ids)
    return json.dumps([str(v) for v in ids])


def _ids_of(payload):
    """The ids a payload carries: a whole-number array (an array payload,
    or the JSON text one turns into) or the JSON list of other ids."""
    if isinstance(payload, ArrayPayload):
        return decode_array(payload, np.int64)
    o = json.loads(payload)
    return np.asarray(o["data"], np.int64) if isinstance(o, dict) else o


class AlsModelDataConverter(SimpleModelDataConverter):
    """reference: common/recommendation/AlsModelDataConverter.java. The
    factors and whole-number ids are ARRAY payloads (``ArrayPayload``:
    where the fit left them, device-resident for a device-resident
    table); a model table written before that, ids in the meta row and
    factors as JSON text, still loads."""

    def serialize_model(self, m: AlsModelData):
        meta = Params({"user_col": m.user_col, "item_col": m.item_col,
                       "rate_col": m.rate_col})
        return meta, [ArrayPayload(m.user_factors),
                      ArrayPayload(m.item_factors),
                      _ids_payload(m.user_ids), _ids_payload(m.item_ids)]

    def deserialize_model(self, meta, data):
        if len(data) >= 4:
            ids = [_ids_of(d) for d in data[2:4]]
        else:                            # the old layout: ids in the meta
            ids = [list(meta._m.get("user_ids", [])),
                   list(meta._m.get("item_ids", []))]
        return AlsModelData(
            ids[0], ids[1], payload_array(data[0]), payload_array(data[1]),
            meta._m.get("user_col", "user"), meta._m.get("item_col", "item"),
            meta._m.get("rate_col", "rating"))


def _index_column(col):
    """``(ids, index, rows)`` of an id column: the distinct ids (a
    whole-number array, or a list), each row's index among them, and how
    many there are. No Python a row: a blocked column of whole numbers is
    its own index (rows ``0 .. max``; an id with no rating gets a row of
    zeros), host whole numbers go through ``np.unique``, anything else
    through ``np.unique`` over the objects or, where they do not sort, a
    dictionary (small tables only)."""
    if isinstance(col, RowBlockColumn):
        if col.dtype.kind not in "iu":
            raise ValueError("a blocked id column must hold whole numbers")
        n = int(col.blocks.max()) + 1 if len(col) else 0
        return np.arange(n, dtype=np.int64), col, n
    arr = np.asarray(col)
    try:
        uniq, inv = np.unique(arr, return_inverse=True)
    except TypeError:                       # mixed objects: a dictionary
        uniq = sorted({_c(v) for v in col}, key=str)
        lookup = {v: i for i, v in enumerate(uniq)}
        inv = np.asarray([lookup[_c(v)] for v in col])
    ids = (uniq.astype(np.int64) if getattr(uniq, "dtype", None) is not None
           and uniq.dtype.kind in "iu" else [_c(v) for v in uniq])
    return ids, inv.reshape(-1).astype(np.int32), len(uniq)


class AlsTrainBatchOp(BatchOperator, HasSeed):
    """reference: batch/recommendation/AlsTrainBatchOp.java"""
    USER_COL = ParamInfo("user_col", str, optional=False)
    ITEM_COL = ParamInfo("item_col", str, optional=False)
    RATE_COL = ParamInfo("rate_col", str, optional=False)
    RANK = ParamInfo("rank", int, default=10, validator=RangeValidator(1, None))
    NUM_ITER = ParamInfo("num_iter", int, default=10,
                         validator=RangeValidator(1, None))
    LAMBDA = ParamInfo("lambda_", float, default=0.1, aliases=("lambda",))
    IMPLICIT_PREFS = ParamInfo("implicit_prefs", bool, default=False)
    ALPHA = ParamInfo("alpha", float, default=40.0)
    NONNEGATIVE = ParamInfo("nonnegative", bool, default=False)
    SHARD_SOLVE = ParamInfo("shard_solve", bool, default=False,
                            description="shard the normal-equation "
                                        "accumulation + solve by id range "
                                        "(reduce_scatter) and all_gather "
                                        "only the solved factors")

    def link_from(self, in_op: BatchOperator) -> "AlsTrainBatchOp":
        with trace_span("als.fit", cat="als", coarse=True) as fit:
            t = in_op.get_output_table()
            uc, ic, rc = (self.get_user_col(), self.get_item_col(),
                          self.get_rate_col())
            with trace_span("als.extract", cat="als", coarse=True):
                user_ids, users, n_users = _index_column(t.col(uc))
                item_ids, items, n_items = _index_column(t.col(ic))
                ratings = t.col(rc)
                if not isinstance(ratings, RowBlockColumn):
                    ratings = np.asarray(ratings, np.float32)
            p = AlsTrainParams(
                rank=self.get_rank(), num_iter=self.get_num_iter(),
                lambda_reg=self.get_lambda_(),
                implicit_prefs=self.get_implicit_prefs(),
                alpha=self.get_alpha(), nonnegative=self.get_nonnegative(),
                seed=self.get_seed(), shard_solve=self.get_shard_solve())
            info: dict = {}
            uf, if_, curve = als_train(users, items, ratings, p,
                                       num_users=n_users, num_items=n_items,
                                       info=info)
            with trace_span("als.model", cat="als", coarse=True):
                if isinstance(uf, np.ndarray):     # a host table's: as ever
                    uf, if_ = uf.astype(np.float64), if_.astype(np.float64)
                model = AlsModelData(user_ids, item_ids, uf, if_, uc, ic, rc)
                self._output = AlsModelDataConverter().save_model(model)
                self._side_outputs = [
                    MTable({"iter": np.arange(1, len(curve) + 1),
                            "train_rmse": curve.astype(np.float64)})]
            self._train_info = info
            fit.set(ratings=int(t.num_rows), rank=int(p.rank))
        if metrics_enabled():
            get_registry().inc("alink_als_fits_total", 1)
        return self

    def get_train_info(self) -> dict:
        """What the last fit went through (``als_train``'s ``info``): the
        factors as the program holds them and the item factors the last
        user half-sweep read (no copy), each side's counts, the RMSE
        curve, the ratings folded and the words that name the paths
        taken."""
        return self._train_info


def _c(v):
    return v.item() if isinstance(v, np.generic) else v


def _id_index(ids) -> dict:
    """id -> row index under both the raw and the string form of the id."""
    lookup: dict = {}
    for i, v in enumerate(ids):
        lookup.setdefault(v, i)
        lookup.setdefault(str(v), i)
    return lookup


def _id_lookup(ids):
    """What ``_encode_ids`` looks ids up in: whole-number ids stay the
    array they are (searched, no dictionary of a million entries), other
    ids become a dictionary."""
    if isinstance(ids, np.ndarray) and ids.dtype.kind in "iu":
        order = np.argsort(ids, kind="stable")
        return ids[order], order
    return _id_index(ids)


def _whole(v):
    """``v`` as the whole number it stands for (``"7"``, ``7.0``), else
    ``None``."""
    try:
        f = float(_c(v))
        return int(f) if f == int(f) else None
    except (TypeError, ValueError, OverflowError):
        return None


def _encode_ids(col, lookup) -> np.ndarray:
    """id -> factor-row encode; -1 for unknown ids.

    The column collapses to its distinct values first (np.unique), so only
    O(distinct) Python-level dict probes run regardless of row count — the
    factor math afterwards is a single gather + einsum. Columns whose
    values don't sort (mixed types) fall back to a memoized row loop."""
    arr = np.asarray(col)
    if isinstance(lookup, tuple):
        sorted_ids, order = lookup
        known = np.ones(arr.shape, bool)
        if arr.dtype.kind not in "iu":      # "7", 7.0: as the dictionary did
            whole = [_whole(v) for v in arr]
            known = np.asarray([w is not None for w in whole], bool)
            arr = np.asarray([w or 0 for w in whole], np.int64)
        if not len(sorted_ids):
            return np.full(len(arr), -1, np.int64)
        at = np.minimum(np.searchsorted(sorted_ids, arr), len(sorted_ids) - 1)
        return np.where(known & (sorted_ids[at] == arr), order[at],
                        -1).astype(np.int64)
    try:
        uniq, inv = np.unique(arr, return_inverse=True)
    except TypeError:
        out = np.empty(len(col), np.int64)
        memo: dict = {}
        for r, v in enumerate(col):
            v = _c(v)
            j = memo.get(v)
            if j is None:
                j = lookup.get(str(v), lookup.get(v, -1))
                memo[v] = j
            out[r] = j
        return out
    codes = np.asarray([lookup.get(str(_c(v)), lookup.get(_c(v), -1))
                        for v in uniq], np.int64)
    return codes[inv.reshape(-1)]


def _load_host_model(model_table: MTable) -> AlsModelData:
    """The model with its factors on the host (device-resident ones are
    fetched once): the predictors here are the host's."""
    m = AlsModelDataConverter().load_model(model_table)
    m.user_factors = np.asarray(m.user_factors)
    m.item_factors = np.asarray(m.item_factors)
    return m


class AlsRater:
    """Loaded ALS factors + id lookups, reusable across calls — the stream
    predict op loads this once and rates every micro-batch with it."""

    def __init__(self, model_table: MTable):
        self.m = m = _load_host_model(model_table)
        # list ids round-trip to strings through the model table, so index
        # both the raw and the str form of each
        self.u_lookup = _id_lookup(m.user_ids)
        self.i_lookup = _id_lookup(m.item_ids)

    def rate_table(self, t: MTable, user_col: str, item_col: str,
                   prediction_col: str, reserved_cols=None) -> MTable:
        m = self.m
        ui = _encode_ids(t.col(user_col), self.u_lookup)
        ii = _encode_ids(t.col(item_col), self.i_lookup)
        valid = (ui >= 0) & (ii >= 0)
        # one gather per side + a row-wise dot; unknown ids -> NaN
        preds = np.einsum("ij,ij->i", m.user_factors[np.maximum(ui, 0)],
                          m.item_factors[np.maximum(ii, 0)])
        preds = np.where(valid, preds, np.nan)
        from ....mapper.base import OutputColsHelper
        helper = OutputColsHelper(t.schema, [prediction_col],
                                  [AlinkTypes.DOUBLE], reserved_cols)
        return helper.build_output(t, [preds])


class AlsPredictBatchOp(BatchOperator, HasPredictionCol, HasReservedCols):
    """Predict the rating of (user, item) rows (reference AlsPredictBatchOp)."""
    USER_COL = ParamInfo("user_col", str, optional=False)
    ITEM_COL = ParamInfo("item_col", str, optional=False)

    def link_from(self, model_op: BatchOperator, data_op: BatchOperator):
        rater = AlsRater(model_op.get_output_table())
        self._output = rater.rate_table(
            data_op.get_output_table(), self.get_user_col(),
            self.get_item_col(), self.params._m.get("prediction_col", "pred"),
            self.params._m.get("reserved_cols"))
        return self


class AlsTopKPredictBatchOp(BatchOperator, HasPredictionCol):
    """Top-K item recommendations per user row (reference AlsTopKPredictBatchOp)."""
    USER_COL = ParamInfo("user_col", str, optional=False)
    TOP_K = ParamInfo("top_k", int, default=10)

    def link_from(self, model_op: BatchOperator, data_op: BatchOperator):
        m = _load_host_model(model_op.get_output_table())
        t = data_op.get_output_table()
        u_lookup = _id_lookup(m.user_ids)
        k = min(self.get_top_k(), len(m.item_ids))
        recs = np.empty(t.num_rows, object)
        # one matmul for all requested users (MXU-sized batch)
        uidx = _encode_ids(t.col(self.get_user_col()), u_lookup)
        valid = uidx >= 0
        scores = m.user_factors[np.maximum(uidx, 0)] @ m.item_factors.T
        top = np.argsort(-scores, axis=1)[:, :k]
        for r in range(t.num_rows):
            if not valid[r]:
                recs[r] = None
                continue
            recs[r] = json.dumps({
                "object": [str(m.item_ids[j]) for j in top[r]],
                "rate": [float(scores[r, j]) for j in top[r]]})
        from ....mapper.base import OutputColsHelper
        helper = OutputColsHelper(t.schema,
                                  [self.params._m.get("prediction_col",
                                                      "recommendations")],
                                  [AlinkTypes.STRING])
        self._output = helper.build_output(t, [recs])
        return self
