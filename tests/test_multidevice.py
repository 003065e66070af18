"""Multi-device behaviour (shard_map pipeline, compressed all-reduce,
mini dry-run) — run in subprocesses with XLA_FLAGS forcing 8 host
devices, so the main test process keeps its single-device view.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

# every test spawns a subprocess with 8 forced host devices (minutes
# each on CPU): nightly/full CI only (the tier1 job deselects `slow`)
pytestmark = pytest.mark.slow

ROOT = Path(__file__).parent.parent


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=480,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    return out.stdout


def test_gpipe_matches_sequential():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
        from repro.distributed.pipeline import gpipe, stage_split

        mesh = make_test_mesh((4,), ("pod",))
        n_stages, layers_per_stage, d = 4, 2, 16

        key = jax.random.key(0)
        ws = jax.random.normal(key, (n_stages, layers_per_stage, d, d)) * 0.3

        def stage_fn(sp, x):
            for i in range(layers_per_stage):
                x = jnp.tanh(x @ sp[i])
            return x

        x = jax.random.normal(jax.random.key(1), (8, d))  # 4 microbatches of 2
        pipelined = gpipe(stage_fn, mesh=mesh, axis="pod", n_microbatches=4)
        y = jax.jit(pipelined)(ws, x)

        ref = x
        for s in range(n_stages):
            ref = stage_fn(ws[s], ref)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)
        print("GPIPE_OK")
    """)


def test_gpipe_gradients_flow():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
        from repro.distributed.pipeline import gpipe

        mesh = make_test_mesh((4,), ("pod",))
        d = 8
        ws = jax.random.normal(jax.random.key(0), (4, d, d)) * 0.3
        x = jax.random.normal(jax.random.key(1), (8, d))

        def stage_fn(sp, h):
            return jnp.tanh(h @ sp)

        pipe = gpipe(stage_fn, mesh=mesh, axis="pod", n_microbatches=4)
        def loss_pipe(ws): return jnp.sum(pipe(ws, x) ** 2)
        def loss_seq(ws):
            h = x
            for s in range(4): h = stage_fn(ws[s], h)
            return jnp.sum(h ** 2)
        g_pipe = jax.jit(jax.grad(loss_pipe))(ws)
        g_seq = jax.grad(loss_seq)(ws)
        np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_seq), atol=1e-4)
        print("GPIPE_GRAD_OK")
    """)


def test_compressed_all_reduce_shard_map():
    _run("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_test_mesh
        from repro.distributed.compression import compressed_all_reduce_mean, ef_init

        mesh = make_test_mesh((8,), ("data",))
        per_rank = jax.random.normal(jax.random.key(0), (8, 32))  # rank r owns row r

        @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                           out_specs=(P("data"), P("data")), check_vma=False)
        def reduce(g, ef):
            out, ef2 = compressed_all_reduce_mean({"g": g}, {"g": ef}, "data")
            return out["g"], ef2["g"]

        ef = jnp.zeros((8, 32))
        got, ef2 = jax.jit(reduce)(per_rank, ef)
        want = jnp.mean(per_rank, axis=0)
        # int8 wire: loose tolerance; every rank must agree exactly
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=0.05)
        np.testing.assert_allclose(np.asarray(got), np.tile(np.asarray(got[0]), (8,1)), atol=1e-7)
        print("CAR_OK")
    """)


def test_mini_dryrun_all_cell_kinds():
    """lower+compile every cell kind on a (2,4) mesh with a smoke config
    — the same steps.build_cell plumbing the 512-device dry-run uses."""
    _run("""
        import jax
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_test_mesh
        from repro.launch.steps import lower_cell
        from repro.models.config import ShapeConfig
        from repro.launch import hlo_analysis

        mesh = make_test_mesh((2, 4), ("data", "model"))
        shapes = [
            ShapeConfig("t", 64, 4, "train"),
            ShapeConfig("p", 64, 4, "prefill"),
            ShapeConfig("d", 64, 4, "decode"),
        ]
        for arch in ("tinyllama-1.1b", "qwen3-moe-235b-a22b", "mamba2-2.7b",
                     "seamless-m4t-large-v2", "internvl2-1b", "jamba-1.5-large-398b"):
            cfg = get_smoke_config(arch)
            for sh in shapes:
                lowered = lower_cell(cfg, sh, mesh)
                compiled = lowered.compile()
                rec = hlo_analysis.analyze_compiled(compiled, mesh.size)
                assert rec["flops_per_dev"] > 0, (arch, sh.name)
                print(arch, sh.name, "ok", f"{rec['flops_per_dev']:.2e}")
        print("MINI_DRYRUN_OK")
    """, devices=8)


def test_elastic_restore_across_meshes():
    """Checkpoint sharded on a (2,4) mesh restores onto (4,2) and (8,)
    meshes — the elastic-scaling path."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile, os
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from repro.launch.mesh import make_test_mesh

        root = tempfile.mkdtemp()
        mesh_a = make_test_mesh((2, 4), ("data", "model"))
        w = jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32)
        w_a = jax.device_put(w, NamedSharding(mesh_a, P("data", "model")))
        mgr = CheckpointManager(root)
        mgr.save(3, {"w": w_a})

        for shape, axes, spec in (
            ((4, 2), ("data", "model"), P("data", "model")),
            ((8,), ("data",), P("data")),
        ):
            mesh_b = make_test_mesh(shape, axes)
            sh = NamedSharding(mesh_b, spec)
            got, extra = mgr.restore({"w": w}, shardings={"w": sh})
            assert extra["step"] == 3
            assert got["w"].sharding == sh
            np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(w))
        print("ELASTIC_OK")
    """)


def test_ep_moe_matches_pjit_reference():
    """Hand-written shard_map EP dispatch == the pjit moe_ffn at
    drop-free capacity (same params, same routing)."""
    _run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.distributed.ep import ep_moe_ffn
        from repro.launch.mesh import make_test_mesh
        from repro.models import moe as moe_lib

        mesh = make_test_mesh((8,), ("model",))
        cfg = dataclasses.replace(
            get_smoke_config("qwen3-moe-235b-a22b"),  # 8 experts top-4 smoke
            moe_capacity_factor=64.0,                  # drop-free
        )
        params = moe_lib.moe_init(jax.random.key(0), cfg)
        x = (jax.random.normal(jax.random.key(1), (8, 16, cfg.d_model)) * 0.5
             ).astype(jnp.bfloat16)

        ref, aux_ref = moe_lib.moe_ffn(params, x, cfg)
        got, aux = jax.jit(
            lambda p, x: ep_moe_ffn(p, x, cfg, mesh)
        )(params, x)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2,
        )
        # gradients flow through the all_to_alls
        def loss(p):
            y, _ = ep_moe_ffn(p, x, cfg, mesh)
            return jnp.sum(y.astype(jnp.float32) ** 2)
        g = jax.jit(jax.grad(loss))(params)
        assert all(bool(jnp.all(jnp.isfinite(l))) for l in jax.tree.leaves(g))
        assert float(jnp.abs(g["w1"]).max()) > 0
        print("EP_OK")
    """)


def test_fleet_replicas_one_device_each():
    """FleetEngine.build places replica i on device i (params, KV pool,
    programs); routed generations equal one replica's, and KV rows that
    cross replicas — a prefix graft and a failover snapshot — move onto
    the receiving replica's device before use."""
    _run("""
        import dataclasses
        import jax, numpy as np
        from repro import compiler
        from repro.configs import get_smoke_config
        from repro.fleet import FleetEngine
        from repro.models import lm as lm_lib
        from repro.serving import PrefixGraft, Request, RequestStatus
        from repro.serving.scheduler import RequestState

        cfg = dataclasses.replace(get_smoke_config("tinyllama-1.1b"), quant="bnn")
        params = lm_lib.init_params(jax.random.key(0), cfg)
        target = compiler.HardwareTarget()
        rng = np.random.default_rng(0)
        shared = rng.integers(1, cfg.vocab_size, (8,), dtype=np.int32)
        prompts = [np.concatenate([shared, rng.integers(1, cfg.vocab_size, (3,), np.int32)])
                   for _ in range(8)]

        def drive(n):
            fleet = FleetEngine.build(cfg, params, target, n_replicas=n,
                                      max_batch=2, max_len=24, block_size=4)
            states = []
            for i, p in enumerate(prompts):
                states.append(fleet.submit(Request(rid=i, prompt=p, max_new_tokens=4)))
                if i >= 3:
                    fleet.step()
            fleet.drain()
            assert all(s.status is RequestStatus.FINISHED for s in states)
            return fleet, [tuple(s.generated) for s in states]

        fleet, got = drive(4)
        devs = []
        for rid, rep in sorted(fleet.replicas.items()):
            held = {d for t in (rep.compiled.params, rep.serving.caches)
                    for x in jax.tree.leaves(t) for d in x.devices()}
            assert len(held) == 1, (rid, held)
            devs.append(held.pop())
        assert devs == jax.devices()[:4], devs
        assert fleet.stats().prefix_hits > 0
        _, want = drive(1)
        assert got == want

        se0, se1, se2 = (fleet.replicas[r].serving for r in range(3))
        # prefix graft: replica 0's rows admitted on replica 2
        _, donor = fleet.replicas[0].compiled.prefill(prompts[0][None, :8])
        rows = jax.tree.map(lambda c: c[:, 0], donor)
        st = se2.submit(Request(rid=100, prompt=prompts[0], max_new_tokens=4,
                                prefix=PrefixGraft(length=8, rows=rows)))
        se2.drain()
        assert tuple(st.generated) == got[0]
        # failover snapshot: evicted on replica 0, resumed on replica 1
        st0 = RequestState(request=Request(rid=101, prompt=prompts[1],
                                           max_new_tokens=4), seq=0, submit_tick=0)
        slot = se0.acquire_slot()
        se0.prefill_into(slot, st0)
        se0.decode_tick({slot: st0})
        snap = se0.evict_slot(slot)
        st1 = se1.scheduler.adopt(st0.request, generated=list(st0.generated),
                                  snapshot=snap)
        se1.drain()
        assert tuple(st1.generated) == got[1]
        print("FLEET_DEVICES_OK")
    """, devices=4)
