"""Compile the hybrid LM cell's decode block and row prefill for a
described TPU v5e (no chip needed) and print what the compiler says each
needs, as ``bench/tools/aot_memory.py`` does for the dense cell.

    JAX_PLATFORMS=cpu python3 bench/tools/aot_memory_hybrid.py \\
        --workload granite-h-int8-poisson --n-slots 48 64

Arguments are shapes only (``jax.eval_shape`` of the bench-made weights,
the engine's pool cache and bit tables).  One line per program and slot
count, in bytes, and the total the decode block holds at once
(arguments + output - aliased + temporaries) beside 15.75 GiB.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LIMIT = 15.75 * 2 ** 30


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="granite-h-int8-poisson")
    ap.add_argument("--n-slots", type=int, nargs="*", default=None)
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, weights_hybrid
    from bench.kinds import lm_hybrid as kind
    from repro.kernels import ops
    from repro.models import lm
    from repro.serve.engine import ServeEngine

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(args.workload)
    m = kind.model_dims(cell.config)
    sv = cell.config["serve"]
    cfg = kind.model_config(m)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    qp = abstract(jax.eval_shape(lambda: weights_hybrid.serve_params(m, 0)))
    ops.set_force_pallas(True)
    L = m["n_layers"]
    for B in args.n_slots or [sv["n_slots"]]:
        eng = ServeEngine(cfg, qp, max_len=sv["max_len"],
                          controller=kind.build_controller(
                              cell.traffic["menu"], L),
                          n_slots=B, prefill_len=sv["prefill_len"],
                          decode_block=sv["decode_block"])
        cache = abstract(jax.eval_shape(
            lambda: lm.empty_cache(cfg, B, sv["max_len"])))
        with eng.compute_ctx():
            dec = eng._decode_scan.lower(
                qp, sds((B, 1), jnp.int32), sds((B,), jnp.int32), cache,
                sds((B, L), jnp.int32), sds((B, L), jnp.int32),
                sds((B,), jnp.float32), sds((B,), jnp.int32),
                abstract(jax.eval_shape(
                    lambda: jax.random.split(jax.random.PRNGKey(0),
                                             sv["decode_block"])))
            ).compile()
            pre = eng._prefill_row.lower(
                qp, sds((1, sv["prefill_len"]), jnp.int32),
                sds((1,), jnp.int32), sds((1, L), jnp.int32),
                sds((1, L), jnp.int32)).compile()
        for name, c in (("decode block", dec), ("prefill row", pre)):
            ma = c.memory_analysis()
            held = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                    - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
            print(f"n_slots {B} {name}: arguments "
                  f"{ma.argument_size_in_bytes} output "
                  f"{ma.output_size_in_bytes} alias {ma.alias_size_in_bytes}"
                  f" temp {ma.temp_size_in_bytes} held {held} "
                  f"({held / 2 ** 30:.2f} GiB of {LIMIT / 2 ** 30:.2f})",
                  flush=True)
        if B == (args.n_slots or [0])[0]:
            txt = dec.as_text()
            print("ssm_update kernels in the decode block:",
                  txt.count("custom_call_target=\"tpu_custom_call\""),
                  "tpu custom calls;", "ssm_update" in txt, flush=True)


if __name__ == "__main__":
    main()
