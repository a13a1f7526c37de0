"""Multi-device mesh plumbing: io/sharding partition rules
(match_partition_rules / state_sharding / device_put_state) and the
ALINK_TPU_MESH_DEVICES session flag.
"""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, PartitionSpec as P

from alink_tpu.common.mlenv import MLEnvironment


# ---------------------------------------------------------------------------
# partition rules + mesh flag
# ---------------------------------------------------------------------------

class TestPartitionRules:
    def test_match_rules_by_path(self):
        from alink_tpu.io.sharding import match_partition_rules
        tree = {"z": np.zeros(8), "n": np.zeros(8),
                "coef": np.zeros((4, 2)), "lr": np.float64(0.1)}
        specs = match_partition_rules(
            ((r"^(z|n)$", P("d")),), tree, default=P())
        assert specs["z"] == P("d") and specs["n"] == P("d")
        assert specs["coef"] == P()
        assert specs["lr"] == P()          # scalars never partition

    def test_unmatched_leaf_raises_without_default(self):
        from alink_tpu.io.sharding import match_partition_rules
        with pytest.raises(ValueError, match="no rule matches"):
            match_partition_rules(((r"^z$", P("d")),),
                                  {"mystery": np.zeros(4)})

    def test_nested_paths_join_with_slash(self):
        from alink_tpu.io.sharding import match_partition_rules
        tree = {"emb": {"in": np.zeros((8, 2)), "out": np.zeros((8, 2))}}
        specs = match_partition_rules(
            ((r"^emb/in$", P("d")), (r".*", P())), tree)
        assert specs["emb"]["in"] == P("d")
        assert specs["emb"]["out"] == P()

    def test_device_put_state_places_on_mesh(self):
        from alink_tpu.io.sharding import device_put_state
        from alink_tpu.operator.stream.onlinelearning.ftrl import (
            ftrl_state_rules)
        mesh = Mesh(np.array(jax.devices()), ("d",))
        tree = {"z": np.zeros(16), "n": np.zeros(16)}
        placed = device_put_state(tree, mesh, ftrl_state_rules(),
                                  default=P())
        assert placed["z"].sharding.spec == P("d")
        assert placed["n"].sharding.spec == P("d")
        assert (np.asarray(placed["z"]) == 0).all()


class TestMeshDevicesFlag:
    def test_default_is_all_devices(self, monkeypatch):
        monkeypatch.delenv("ALINK_TPU_MESH_DEVICES", raising=False)
        env = MLEnvironment()
        assert env.num_workers == len(jax.devices())

    def test_flag_caps_device_count(self, monkeypatch):
        monkeypatch.setenv("ALINK_TPU_MESH_DEVICES", "4")
        env = MLEnvironment()
        assert env.num_workers == 4
        assert env.mesh.devices.size == 4

    def test_flag_beyond_available_raises(self, monkeypatch):
        monkeypatch.setenv("ALINK_TPU_MESH_DEVICES", "64")
        with pytest.raises(ValueError, match="ALINK_TPU_MESH_DEVICES"):
            MLEnvironment()

    def test_explicit_devices_bypass_flag(self, monkeypatch):
        monkeypatch.setenv("ALINK_TPU_MESH_DEVICES", "2")
        env = MLEnvironment(devices=jax.devices()[:3], parallelism=3)
        assert env.num_workers == 3
