"""The controls at a size a test can hold: the references in bfloat16, and
with each fault planted, read not correct against the cells' own limits;
and the references agree with ``alink_tpu`` where both are right."""

import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _tiny(cell):
    from benchmark import run as R
    found = R.load_cell(cell, parked=True)
    return R.tiny(found["config"]), R.tiny(found["traffic"])


def _fails(readings, limits):
    return {k for k, v in readings.items() if v > float(limits[k])}


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 3, 77])
def test_drain_controls_and_faults_fail_and_float32_passes(seed):
    from benchmark import controls
    config, traffic = _tiny("ftrl-drain")
    got = controls.drain_readings(seed, config, traffic)
    lim = config["limits"]
    assert not _fails(got["float32_again"], lim)
    assert "w_worst_gap" in _fails(got["bfloat16"], lim)
    assert {"dw_norm_gap", "w_worst_gap"} <= _fails(got["half_batch_left_out"], lim)
    assert {"dw_norm_gap", "w_worst_gap"} <= _fails(got["state_unchanged"], lim)
    assert got["state_unchanged"]["dw_norm_gap"] == pytest.approx(1.0)
    assert got["bfloat16"]["untouched_gap"] > float(lim["untouched_gap"])


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 3, 77])
def test_serve_controls_fail_and_float32_passes(seed):
    from benchmark import controls
    config, traffic = _tiny("serve-steady")
    got = controls.serve_readings(seed, config, traffic)
    lim = config["limits"]
    assert not _fails(got["float32"], lim)
    assert _fails(got["bfloat16"], lim) == {"prob_gap"}
    assert _fails(got["one_answer_altered"], lim) == {"prob_gap"}
    assert got["bfloat16"]["prob_gap"] > 3 * float(lim["prob_gap"])


def test_logistic_reference_agrees_with_the_programs_host_mapper():
    from alink_tpu.common.mtable import MTable
    from alink_tpu.common.params import Params
    from alink_tpu.common.types import AlinkTypes, TableSchema
    from alink_tpu.common.vector import SparseVectorColumn
    from alink_tpu.operator.common.linear.base import (
        LinearModelData, LinearModelDataConverter, LinearModelType)
    from alink_tpu.operator.common.linear.mapper import LinearModelMapper
    from benchmark import data
    from benchmark.reference import logistic
    config, _ = _tiny("serve-steady")
    dim = 1 << config["dim_log2"]
    idx, val, _ = data.make_rows(5, 64, config["row_shape"], dim - 1)
    coef = data.host_weights(5, dim, 0.3, 1).astype(np.float64)
    table = MTable({"vec": SparseVectorColumn(idx, val, dim - 1)},
                   TableSchema.parse("vec VECTOR"))
    mapper = LinearModelMapper(
        LinearModelDataConverter(AlinkTypes.LONG).schema, table.schema,
        Params({"vector_col": "vec", "prediction_col": "pred",
                "prediction_detail_col": "detail"}))
    mapper.model = LinearModelData(
        model_name="m", linear_model_type=LinearModelType.LR,
        has_intercept=True, vector_col="vec", feature_names=None,
        vector_size=dim - 1, coef=coef, label_values=[1, 0],
        label_type=AlinkTypes.LONG)
    out = mapper.map_table(table)
    got = np.array([json.loads(s)["1"] for s in out.col("detail")])
    want = logistic.score(coef[1:][idx], val, coef[0], "float64")
    assert np.abs(got - want).max() < 1e-12


def test_ftrl_reference_follows_the_papers_update_by_hand():
    """Two samples on three coordinates, worked in plain Python."""
    import math
    from benchmark.reference import ftrl
    hp = {"alpha": 0.5, "beta": 1.0, "l1": 0.0, "l2": 0.0}
    idx = np.array([[0, 1], [1, 2]], np.int32)
    val = np.array([[1.0, 2.0], [1.0, 1.0]], np.float32)
    y = np.array([1.0, 0.0], np.float32)
    z = [0.0, 0.0, 0.0]
    n = [0.0, 0.0, 0.0]
    for r in range(2):
        w = [-(z[i]) / ((1.0 + math.sqrt(n[i])) / 0.5) for i in idx[r]]
        p = 1 / (1 + math.exp(-sum(v * wi for v, wi in zip(val[r], w))))
        for k, i in enumerate(idx[r]):
            g = (p - y[r]) * val[r][k]
            sigma = (math.sqrt(n[i] + g * g) - math.sqrt(n[i])) / 0.5
            z[i] += g - sigma * w[k]
            n[i] += g * g
    z1, n1 = ftrl.run(idx, val, y, np.zeros(3, np.float32),
                      np.zeros(3, np.float32), hp)
    assert np.allclose(np.asarray(z1), z, rtol=1e-5, atol=1e-7)
    assert np.allclose(np.asarray(n1), n, rtol=1e-5, atol=1e-7)
    w = np.asarray(ftrl.weights(z1, n1, **hp))
    assert w[0] > 0 > w[2], "a click raises the weight, a miss lowers it"
