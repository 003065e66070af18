"""Chip smoke run: serve qwen1.5-0.5b at its published widths on a TPU
through the packed Pallas path, and check it against the reference.

    python chip_smoke.py            # one chip
    python chip_smoke.py --fleet    # four chips: 4 replicas vs 1 replica

Default phase (one chip): build ``qwen1.5-0.5b`` with ``quant="bnn"``
and seeded random params, ``compile()`` it for
``HardwareTarget(engine="packed")`` (fused decode kernel, prepared
weights) and serve 8 seeded requests (prompts of 128 and 256 tokens, 32
new tokens each) through the request scheduler. Every request must
finish, and its token ids must equal those of the plain-jnp
``reference`` target on the same params and requests (the repo's
bit-exact contract). The kernels must really run: interpret mode is
off and the compiled decode step's HLO holds ``tpu_custom_call``.

``--fleet`` (four chips) runs only the fleet path: 16 seeded requests
sharing a 128-token prefix under ``routing="prefix"`` on 4 replicas,
one per chip, against the same requests on 1 replica.

Exits non-zero without a result line when JAX's first device is not a
TPU. Any failed phase raises. The last stdout line is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``. The seconds
and bytes printed before it are informational wall times of a smoke
run, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen1.5-0.5b"
SEED = 0
MAX_BATCH = 8
PROMPT_LENS = (128, 256)  # each distinct length is one prefill compile
GEN = 32
N_REQUESTS = 8
FLEET_REPLICAS = 4
FLEET_REQUESTS = 16
FLEET_PREFIX = 128
FLEET_SUFFIX = 32
FLEET_BATCH = 4


class SmokeError(RuntimeError):
    """A smoke phase produced a wrong or incomplete result."""


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def record(out: dict, key: str, value) -> None:
    """Keep a phase's number and print it at once, so a later failure
    does not hide it."""
    out[key] = value
    log(f"{key} = {value}")


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def make_prompts(vocab: int, lens, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, (n,), dtype=np.int32) for n in lens]


def serve_requests(se, prompts, gen: int, rid0: int = 0) -> list[list[int]]:
    """Submit every prompt, drain, and return each request's tokens;
    raises unless every request ends FINISHED."""
    from repro.serving import Request, RequestStatus

    states = [
        se.submit(Request(rid=rid0 + i, prompt=p, max_new_tokens=gen))
        for i, p in enumerate(prompts)
    ]
    se.drain()
    bad = [(s.rid, s.status.value) for s in states if s.status is not RequestStatus.FINISHED]
    if bad:
        raise SmokeError(f"requests did not finish: {bad}")
    return [list(s.generated) for s in states]


def check_same(got, want, what: str) -> None:
    for i, (g, w) in enumerate(zip(got, want, strict=True)):
        if g != w:
            at = next(j for j, (a, b) in enumerate(zip(g, w)) if a != b)
            raise SmokeError(
                f"{what}: request {i} differs first at token {at}: "
                f"{g[at:at + 8]} vs {w[at:at + 8]}"
            )


def packed_vs_reference(cfg, params, prompts, gen: int, max_batch: int) -> dict:
    """The default phase: serve on the packed target, check its kernels
    ran compiled, and compare every request with the reference target."""
    import jax
    import jax.numpy as jnp

    from repro import compiler
    from repro.kernels import _compat

    if _compat.default_interpret():
        raise SmokeError("Pallas interpret mode is on: the kernels would not compile")
    max_len = max(len(p) for p in prompts) + gen
    out = {}

    t0 = time.perf_counter()
    packed = compiler.compile(cfg, params, compiler.HardwareTarget(engine="packed"))
    jax.block_until_ready(packed.params)
    record(out, "program_s", time.perf_counter() - t0)

    tok = jnp.zeros((max_batch,), jnp.int32)
    t0 = time.perf_counter()
    hlo = packed.lower_decode_step(
        tok, tok, packed.init_cache(max_batch, max_len)
    ).compile().as_text()
    record(out, "decode_compile_s", time.perf_counter() - t0)
    record(out, "decode_custom_calls", hlo.count("tpu_custom_call"))
    if "tpu_custom_call" not in hlo:
        raise SmokeError("the compiled decode step calls no Pallas kernel")

    se = packed.serve(max_batch=max_batch, max_len=max_len)
    t0 = time.perf_counter()
    got = serve_requests(se, prompts, gen)
    record(out, "packed_first_drain_s", time.perf_counter() - t0)
    t0 = time.perf_counter()
    again = serve_requests(se, prompts, gen, rid0=len(prompts))
    record(out, "packed_warm_drain_s", time.perf_counter() - t0)
    check_same(again, got, "packed, second drain vs first")
    del se, packed

    ref = compiler.compile(cfg, params, compiler.HardwareTarget())
    se = ref.serve(max_batch=max_batch, max_len=max_len)
    t0 = time.perf_counter()
    want = serve_requests(se, prompts, gen)
    record(out, "reference_first_drain_s", time.perf_counter() - t0)
    check_same(got, want, "packed vs reference")
    record(out, "tokens", sum(len(g) for g in got))
    return out


def fleet_prompts(vocab: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    shared = rng.integers(1, vocab, (FLEET_PREFIX,), dtype=np.int32)
    return [
        np.concatenate([shared, rng.integers(1, vocab, (FLEET_SUFFIX,), dtype=np.int32)])
        for _ in range(FLEET_REQUESTS)
    ]


def serve_fleet(fleet, prompts, gen: int, upfront: int) -> list[list[int]]:
    """``upfront`` requests at once (spread by load over the replicas,
    no prefix library yet), one step so their prefills fill the
    libraries, then one arrival per fleet tick so the rest graft the
    shared prefix. The same ``upfront`` for every fleet size keeps
    which requests graft identical across the runs compared."""
    from repro.serving import Request, RequestStatus

    states = []
    for i, p in enumerate(prompts):
        states.append(fleet.submit(Request(rid=i, prompt=p, max_new_tokens=gen)))
        if i + 1 >= upfront:
            fleet.step()
    fleet.drain()
    bad = [(s.rid, s.status.value) for s in states if s.status is not RequestStatus.FINISHED]
    if bad:
        raise SmokeError(f"fleet requests did not finish: {bad}")
    return [list(s.generated) for s in states]


def replica_devices(fleet) -> list:
    """The one device each replica's params and KV pool sit on."""
    import jax

    out = []
    for rid, rep in sorted(fleet.replicas.items()):
        devs = {
            d
            for tree in (rep.compiled.params, rep.serving.caches)
            for x in jax.tree.leaves(tree)
            for d in x.devices()
        }
        if len(devs) != 1:
            raise SmokeError(f"replica {rid} spans devices {sorted(map(str, devs))}")
        out.append(devs.pop())
    return out


def fleet_vs_one(cfg, params, prompts, gen: int, n_replicas: int, max_batch: int) -> dict:
    """The --fleet phase: N replicas (one per device) under prefix
    routing vs the same requests on one replica."""
    from repro import compiler
    from repro.fleet import FleetEngine

    max_len = max(len(p) for p in prompts) + gen
    target = compiler.HardwareTarget(engine="packed")
    out = {}
    runs = {}
    for n in (n_replicas, 1):
        t0 = time.perf_counter()
        fleet = FleetEngine.build(
            cfg, params, target, n_replicas=n, max_batch=max_batch,
            max_len=max_len, routing="prefix",
        )
        if n == n_replicas:
            devs = replica_devices(fleet)
            if len(set(devs)) != n_replicas:
                raise SmokeError(f"{n_replicas} replicas share devices: {devs}")
            record(out, "replica_devices", [str(d) for d in devs])
        runs[n] = serve_fleet(fleet, prompts, gen, upfront=n_replicas)
        st = fleet.stats()
        record(out, f"replicas{n}_s", time.perf_counter() - t0)
        record(out, f"replicas{n}_prefix_hits", st.prefix_hits)
        record(out, f"replicas{n}_grafted_tokens", st.grafted_tokens)
        del fleet
    check_same(runs[n_replicas], runs[1], f"{n_replicas} replicas vs 1 replica")
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--fleet", action="store_true",
        help=f"run only the {FLEET_REPLICAS}-replica fleet path (needs "
        f"{FLEET_REPLICAS} chips) against 1 replica",
    )
    args = ap.parse_args(argv)

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(
            f"chip_smoke: JAX's first device is {device.platform!r}, not a "
            "TPU; nothing was run", file=sys.stderr,
        )
        return 1

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import lm as lm_lib

    t_start = time.perf_counter()
    log(f"device {device.device_kind} x{len(jax.devices())}; compile cache {use_compile_cache()}")
    log("seconds printed are informational chip wall times of a smoke run, "
        "not benchmark numbers")
    cfg = get_config(ARCH, quant="bnn")
    params = lm_lib.init_params(jax.random.key(SEED), cfg)
    if args.fleet:
        if len(jax.devices()) < FLEET_REPLICAS:
            raise SmokeError(f"--fleet needs {FLEET_REPLICAS} devices, have {len(jax.devices())}")
        res = fleet_vs_one(
            cfg, params, fleet_prompts(cfg.vocab_size, SEED + 1), GEN,
            FLEET_REPLICAS, FLEET_BATCH,
        )
    else:
        lens = [PROMPT_LENS[i % len(PROMPT_LENS)] for i in range(N_REQUESTS)]
        res = packed_vs_reference(
            cfg, params, make_prompts(cfg.vocab_size, lens, SEED + 1), GEN, MAX_BATCH,
        )
    record(res, "total_s", time.perf_counter() - t_start)
    record(res, "peak_bytes_in_use", _peak_bytes(device))
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
