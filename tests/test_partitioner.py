"""Partitioner rules: divisibility fallback, FSDP+TP+EP specs, cache SP.

Uses AbstractMesh — no devices needed, same spec inference the dry-run
runs on 512 devices.
"""

import jax
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config, get_smoke_config
from repro.distributed import (
    batch_specs,
    cache_specs,
    infer_specs,
    opt_state_specs,
    validate_specs,
)
from repro.launch.steps import default_opt_cfg, opt_shapes, param_shapes
from repro.models import lm as lm_lib
from repro.models.config import SHAPES

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _leaf(tree, *path):
    for p in path:
        tree = tree[p]
    return tree


def test_dense_arch_specs():
    cfg = get_config("qwen2-72b")
    sds = param_shapes(cfg)
    specs = infer_specs(sds, MESH)
    assert not validate_specs(sds, specs, MESH)
    # TP on head projections, FSDP on the other dim
    assert _leaf(specs, "blocks", "slot0", "attn", "q", "w") == P(None, "data", "model")
    assert _leaf(specs, "blocks", "slot0", "attn", "o", "w") == P(None, "model", "data")
    assert _leaf(specs, "blocks", "slot0", "ffn", "w2", "w") == P(None, "model", "data")
    # vocab 152064 divides 16 -> vocab-parallel embed
    assert _leaf(specs, "embed") == P("model", "data")


def test_vocab_padding_makes_tables_shardable():
    cfg = get_config("internvl2-1b")  # vocab 151655 is odd...
    assert cfg.padded_vocab == 151808 and cfg.padded_vocab % 256 == 0
    sds = param_shapes(cfg)
    specs = infer_specs(sds, MESH)
    assert not validate_specs(sds, specs, MESH)
    # ...but the padded table shards vocab-parallel anyway
    assert _leaf(specs, "embed") == P("model", "data")


def test_vocab_fallback_when_indivisible():
    """The divisibility-fallback mechanism itself (synthetic odd table)."""
    sds = {"embed": jax.ShapeDtypeStruct((151655, 896), "float32")}
    specs = infer_specs(sds, MESH)
    assert specs["embed"] == P(None, "model")  # d 896 % 16 == 0, V odd


def test_moe_expert_parallel_vs_fallback():
    # qwen3: 128 experts % 16 == 0 -> EP over model
    specs = infer_specs(param_shapes(get_config("qwen3-moe-235b-a22b")), MESH)
    assert _leaf(specs, "blocks", "slot0", "moe", "w1") == P(None, "model", "data", None)
    # grok: 8 experts on a 16-way axis -> fallback to f-dim TP
    specs_g = infer_specs(param_shapes(get_config("grok-1-314b")), MESH)
    assert _leaf(specs_g, "blocks", "slot0", "moe", "w1") == P(None, None, "data", "model")


def test_mamba_specs():
    cfg = get_config("mamba2-2.7b")
    sds = param_shapes(cfg)
    specs = infer_specs(sds, MESH)
    assert not validate_specs(sds, specs, MESH)
    assert _leaf(specs, "blocks", "slot0", "mamba", "x_proj", "w") == P(None, "data", "model")
    assert _leaf(specs, "blocks", "slot0", "mamba", "out_proj", "w") == P(None, "model", "data")
    # dt/A/D head-sharded: 80 heads % 16 == 0
    assert _leaf(specs, "blocks", "slot0", "mamba", "A_log") == P(None, "model")


def test_opt_state_inherits_param_specs():
    cfg = get_config("jamba-1.5-large-398b")
    opt_cfg = default_opt_cfg(cfg)
    assert opt_cfg.factored  # 398B -> factored second moment
    sds = param_shapes(cfg)
    specs = infer_specs(sds, MESH)
    o_sds = opt_shapes(sds, opt_cfg)
    o_specs = opt_state_specs(specs, o_sds)
    assert o_specs["step"] == P()
    assert not validate_specs(o_sds["m"], o_specs["m"], MESH)
    assert not validate_specs(o_sds["v"], o_specs["v"], MESH)


def test_batch_specs_multipod():
    specs = {"tokens": jax.ShapeDtypeStruct((256, 4096), "int32")}
    b = batch_specs(specs, MESH_MP)
    assert b["tokens"] == P(("pod", "data"), None)
    # batch=1 can't shard -> replicated
    one = batch_specs({"x": jax.ShapeDtypeStruct((1, 8), "float32")}, MESH_MP)
    assert one["x"] == P()


def test_cache_specs_sequence_parallel():
    cfg = get_smoke_config("tinyllama-1.1b")
    cache = jax.eval_shape(lambda: lm_lib.init_cache(cfg, 128, 32768))
    cs = cache_specs(cache, MESH)
    k = cs["slot0"]["k"]  # (L, B, T, KV, D): batch over data, T over model
    assert k == P(None, "data", "model", None, None)
    # batch=1 long-context: T takes (data, model)
    cache1 = jax.eval_shape(lambda: lm_lib.init_cache(cfg, 1, 524288))
    cs1 = cache_specs(cache1, MESH)
    assert cs1["slot0"]["k"] == P(None, None, ("data", "model"), None, None)


def test_all_archs_validate_on_both_meshes():
    from repro.configs import ARCH_IDS

    for arch in ARCH_IDS:
        sds = param_shapes(get_config(arch))
        for mesh in (MESH, MESH_MP):
            specs = infer_specs(sds, mesh)
            problems = validate_specs(sds, specs, mesh)
            assert not problems, f"{arch}: {problems[:3]}"
