"""Compile the main path's Pallas kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler that ships with jaxlib compiles
for a ``v5e:2x2`` topology description, so Mosaic's refusals (block
shapes off the (8, 128) tile, unsupported reductions or reshapes) show
up here instead of on the chip. Shapes are qwen1.5-0.5b's binarized
projections (q/o 1024->1024, fused q|k|v 1024->3072, w1/w3 1024->2816,
w2 2816->1024) at decode (8) and prefill (256) rows.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and the worker that runs
this file keeps it until it exits. The persistent compilation cache is
off around these compiles: an entry written for a described chip
cannot be read back without one.
"""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention

PROJECTIONS = [(1024, 1024), (1024, 3072), (1024, 2816), (2816, 1024)]
ROWS = [8, 256]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """ShapeDtypeStruct factory on one described chip, with the
    persistent compilation cache off for the module's compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("m,n", PROJECTIONS)
def test_fused_bnn_matmul_compiles(chip, rows, m, n):
    kw = -(-m // 32)
    hlo = _compiled_hlo(
        lambda x, w, a: ops.fused_bnn_matmul(x, w, a, m=m, n=n, interpret=False),
        chip((rows, m), jnp.bfloat16),
        chip((kw, n), jnp.int32),
        chip((n,), jnp.float32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("m,n", PROJECTIONS)
def test_xnor_matmul_packed_weights_compiles(chip, rows, m, n):
    kw = -(-m // 32)
    hlo = _compiled_hlo(
        lambda a, w: ops.xnor_matmul_packed_weights(a, w, m=m, n=n, interpret=False),
        chip((rows, m), jnp.float32),
        chip((kw, n), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("sq", [1, 256])
def test_flash_attention_compiles(chip, sq):
    """qwen1.5-0.5b attention: 16 heads (MHA) of 64, 256 cached keys."""
    hlo = _compiled_hlo(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False),
        chip((1, 16, sq, 64), jnp.bfloat16),
        chip((1, 16, 256, 64), jnp.bfloat16),
        chip((1, 16, 256, 64), jnp.bfloat16),
    )
    assert "tpu_custom_call" in hlo


def test_chip_smoke_refuses_cpu(capsys):
    """Without a TPU the smoke script exits non-zero and prints no
    result line (this suite runs with JAX on the CPU)."""
    path = Path(__file__).resolve().parent.parent / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)

    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
