"""Compile an LM cell's decode block and row prefill for a described TPU
v5e (no chip needed) and print what the compiler says each needs.

    JAX_PLATFORMS=cpu python3 bench/tools/aot_memory.py --workload \\
        lm-int8-poisson

Arguments are shapes only (``jax.eval_shape`` of the bench-made weights,
the engine's pool cache and bit tables), so nothing of full size is made
on the host.  Prints ``memory_analysis()`` of each program in bytes.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="lm-int8-poisson")
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, weights
    from bench.kinds import lm as kind
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

    qp = abstract(jax.eval_shape(lambda: weights.lm_serve_params(m, 0)))
    eng = ServeEngine(cfg, qp, max_len=sv["max_len"],
                      controller=kind.build_controller(cell.traffic["menu"],
                                                      m["n_layers"]),
                      n_slots=sv["n_slots"], prefill_len=sv["prefill_len"],
                      decode_block=sv["decode_block"])
    B, L = sv["n_slots"], m["n_layers"]
    cache = abstract(jax.eval_shape(
        lambda: lm.empty_cache(cfg, B, sv["max_len"])))

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    ops.set_force_pallas(True)
    with eng.compute_ctx():
        dec = eng._decode_scan.lower(
            qp, sds((B, 1), jnp.int32), sds((B,), jnp.int32), cache,
            sds((B, L), jnp.int32), sds((B, L), jnp.int32),
            sds((B,), jnp.float32), sds((B,), jnp.int32),
            abstract(jax.eval_shape(
                lambda: jax.random.split(jax.random.PRNGKey(0),
                                         sv["decode_block"])))).compile()
        pre = eng._prefill_row.lower(
            qp, sds((1, sv["prefill_len"]), jnp.int32), sds((1,), jnp.int32),
            sds((L,), jnp.int32), sds((L,), jnp.int32)).compile()
    for name, c in (("decode block", dec), ("prefill row", pre)):
        ma = c.memory_analysis()
        print(f"{name}: arguments {ma.argument_size_in_bytes} output "
              f"{ma.output_size_in_bytes} temp {ma.temp_size_in_bytes} "
              f"alias {ma.alias_size_in_bytes} bytes", flush=True)


if __name__ == "__main__":
    main()
