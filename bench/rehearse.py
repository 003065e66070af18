"""Compile a cell's serving programs for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell>

Builds the programmed parameter shapes of the cell's configuration
(``jax.eval_shape`` over weight making and ``compile()``: nothing is
allocated), then lowers and compiles, for one chip of a described
``v5e:2x2``, the prefill program at each prompt length of the mix and
the gathered decode program at the pool's shape, with the Pallas
kernels compiled by Mosaic, not interpreted. Prints each program's
``memory_analysis()``: a compile, not a chip measurement.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--max-batch", type=int, help="size another pool than the mix's")
    ap.add_argument("--decode-only", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    import reference
    import weights
    from repro import compiler
    from repro.kernels import _compat
    from repro.models import lm
    from repro.models.layers import EXACT_COMPILER_OPTIONS

    jax.config.update("jax_enable_compilation_cache", False)
    # compile the kernels as Mosaic would on the chip, not interpreted
    _compat.default_interpret = lambda: False

    cell = harness.load_cell(args.workload)
    mix = cell.traffic
    dims = reference.Dims.from_config(cell.config)
    cfg = harness.model_config(cell.config)
    b, max_len = args.max_batch or mix["max_batch"], mix["max_len"]
    target = compiler.HardwareTarget(engine="packed")

    def programmed(keys):
        params = weights.program_params(keys, dims)
        return compiler.compile(cfg, params, target).params

    keys = jax.eval_shape(lambda: weights.table_keys(0))
    p_shapes = jax.eval_shape(programmed, keys)
    cm = compiler.compile(cfg, None, target)
    ex = cm.executor(b)
    cfg = cm.cfg

    one = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0]
    )
    on_chip = functools.partial(jax.tree.map, lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one))
    params = on_chip(p_shapes)
    caches = on_chip(jax.eval_shape(lambda: lm.init_cache(cfg, b, max_len)))
    jit = functools.partial(jax.jit, compiler_options=EXACT_COMPILER_OPTIONS)
    out = {}

    def report(name, lowered):
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        out[name] = {
            k: getattr(mem, k)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
        }
        out[name]["custom_calls"] = compiled.as_text().count("tpu_custom_call")
        print(name, json.dumps(out[name]), flush=True)

    for length in [] if args.decode_only else sorted(int(p) for p in mix["prompt_lens"]):
        tokens = jax.ShapeDtypeStruct((1, length), jnp.int32, sharding=one)
        report(f"prefill_{length}", jit(
            lambda p, t: lm.prefill(p, t, cfg, engine=ex)
        ).lower(params, tokens))

    def gathered_decode(p, tok, pos, caches, idx):
        gathered = jax.tree.map(lambda c: jnp.take(c, idx, axis=1), caches)
        logits, new_c = lm.decode_step(p, tok[idx], pos[idx], gathered, cfg, engine=ex)
        return logits, jax.tree.map(lambda d, s: d.at[:, idx].set(s.astype(d.dtype)), caches, new_c)

    vec = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one)
    report("decode_gathered", jit(gathered_decode, donate_argnums=(3,)).lower(
        params, vec, vec, caches, vec))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
