"""Activation-hint resolution logic (mesh-agnostic parts)."""

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh

from repro.distributed import hints

MESH = AbstractMesh((2, 8, 4), ("pod", "data", "model"))


def test_hint_is_noop_outside_context():
    x = jnp.ones((4, 4))
    assert hints.hint(x, "dp", "model") is x


def test_resolve_dp_default_and_override():
    with hints.activation_hints(MESH):
        assert hints._resolve("dp", MESH) == ("pod", "data")
        assert hints._resolve("dp_strict", MESH) == ("pod", "data")
        assert hints._resolve("model", MESH) == "model"
    with hints.activation_hints(MESH, batch_axes=("data", "model"), tp=False):
        assert hints._resolve("dp", MESH) == ("data", "model")
        assert hints._resolve("dp_strict", MESH) == ("pod", "data")  # ignores override
        assert hints._resolve("model", MESH) is None                 # tp off
        assert hints._resolve("model_strict", MESH) == "model"       # survives tp off


def test_axis_size():
    assert hints._axis_size(("pod", "data"), MESH) == 16
    assert hints._axis_size("model", MESH) == 4
    assert hints._axis_size(None, MESH) == 1


def test_indivisible_dims_drop_to_replicated():
    """hint() must silently drop axes that don't divide the dim."""
    mesh = AbstractMesh((4,), ("model",))
    with hints.activation_hints(mesh):
        # 4 divides 8 -> spec applies; 4 does not divide 6 -> dropped
        r8 = hints._resolve("model", mesh)
        assert r8 == "model"
        # the division check lives in hint(); emulate it:
        assert 8 % hints._axis_size(r8, mesh) == 0
        assert 6 % hints._axis_size(r8, mesh) != 0
