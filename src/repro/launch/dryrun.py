"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and extract roofline inputs from the compiled artifact.

A CPU lowering tool: it pins the CPU platform (for itself and, through
the environment, every ``--all`` child) so it never claims an
accelerator, and it MUST set the fake-device flag before ANY jax import
(jax locks the device count on first init):
"""
import os
import re
os.environ["JAX_PLATFORMS"] = "cpu"
# Drop any inherited device-count flag (CI exports =8 for the mesh tests;
# whichever flag comes LAST wins inside XLA) before forcing 512.
_inherited = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                    os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (_inherited.strip()
                           + " --xla_force_host_platform_device_count=512"
                           ).strip()

import argparse           # noqa: E402
import json               # noqa: E402
import subprocess         # noqa: E402
import sys                # noqa: E402
import time               # noqa: E402

import jax                # noqa: E402
import jax.numpy as jnp   # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs                                   # noqa: E402
from repro.dist import sharding as shd                      # noqa: E402
from repro.launch import hloparse                           # noqa: E402
from repro.launch import specs as sp                        # noqa: E402
from repro.launch.mesh import (HBM_BW, HBM_PER_CHIP, ICI_BW,  # noqa: E402
                               PEAK_FLOPS_BF16, make_production_mesh)
from repro.models import lm                                 # noqa: E402
from repro.models.config import SHAPES_BY_NAME              # noqa: E402
from repro.train.loop import TrainConfig, make_train_step   # noqa: E402

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "f16": 2, "bf16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 0.5, "u4": 0.5}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


# ---------------------------------------------------------------------------
# Cell planning
# ---------------------------------------------------------------------------

def planned_cells():
    """All (arch, shape) cells; long_500k only for sub-quadratic archs
    (skip recorded in DESIGN.md §4)."""
    cells = []
    for arch in configs.ARCH_IDS:
        cfg = configs.get(arch)
        for s in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
            if s == "long_500k" and not cfg.subquadratic:
                continue
            cells.append((arch, s))
    return cells


def accum_for(cfg, shape) -> int:
    """Microbatch count (the §Perf accumulation knob).

    Measured on qwen1.5-110b (§Perf iter 3): accum 8 -> 2 cut collective
    only 25.5 -> 20.3 s (XLA already hoists the gradient all-reduce out of
    the microbatch scan, so only the FSDP weight regather scales) while
    activation peak blew 36 -> 127 GiB.  REFUTED trade — 8 stays."""
    if shape.kind != "train":
        return 1
    return 8


# ---------------------------------------------------------------------------
# HLO collective accounting
# ---------------------------------------------------------------------------

def _shape_bytes(type_str: str) -> float:
    total = 0.0
    for m in re.finditer(r"([a-z0-9]+)\[([\d,]*)\]", type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo: str) -> dict:
    """Per-device bytes moved per collective kind, from result shapes.

    Approximation (documented in EXPERIMENTS.md §Roofline): traffic factor
    2x for all-reduce (ring reduce+broadcast), 1x otherwise; '-start'
    variants counted, '-done' skipped."""
    out = {}
    for m in re.finditer(
            r"=\s+((?:\([^)]*\)|[a-z0-9]+\[[\d,]*\][^\s]*))\s+"
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)(-start|-done)?\(", hlo):
        type_str, kind, suffix = m.group(1), m.group(2), m.group(3)
        if suffix == "-done":
            continue
        b = _shape_bytes(type_str)
        d = out.setdefault(kind, {"count": 0, "bytes": 0.0})
        d["count"] += 1
        d["bytes"] += b
    return out


def collective_traffic_bytes(stats: dict) -> float:
    t = 0.0
    for kind, d in stats.items():
        factor = 2.0 if kind == "all-reduce" else 1.0
        t += factor * d["bytes"]
    return t


# ---------------------------------------------------------------------------
# Analytic FLOPs (roofline numerator sanity check)
# ---------------------------------------------------------------------------

def param_counts(cfg) -> dict:
    p = sp.abstract_params(cfg)
    flat = jax.tree_util.tree_flatten_with_path(p)[0]
    total = emb = expert = 0
    for path, leaf in flat:
        n = 1
        for d in leaf.shape:
            n *= d
        keys = tuple(str(getattr(q, "key", q)) for q in path)
        total += n
        if keys[-1] == "emb" or "head" in keys:
            emb += n
        if "experts" in keys:
            expert += n
    active = total - expert
    if cfg.n_experts:
        active += expert * cfg.experts_per_token / cfg.n_experts
    return {"total": total, "embedding": emb, "active": active}


def model_flops(cfg, shape, counts) -> float:
    """6*N_active*D train; 2*N_active*D forward (prefill/decode)."""
    n = counts["active"] - counts["embedding"]
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch            # decode: one token


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               container: str = "int8", kv_bits: int = 0):
    cfg = configs.get(arch)
    shape = SHAPES_BY_NAME[shape_name]
    if kv_bits and shape.kind != "train":
        cfg = cfg.with_(kv_cache_bits=kv_bits)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            ocfg = sp.optimizer_for(cfg)
            tcfg = TrainConfig(optimizer=ocfg, n_accum=accum_for(cfg, shape))
            params = sp.abstract_params(cfg)
            opt = sp.abstract_opt(cfg, ocfg)
            batch = sp.input_specs(cfg, shape)
            p_shd = shd.param_shardings(params, mesh)
            o_shd = shd.opt_shardings(opt, mesh)
            b_shd = shd.batch_shardings(batch, mesh)
            step_fn, _ = make_train_step(tcfg, cfg, param_shardings=p_shd)
            lowered = jax.jit(
                step_fn,
                in_shardings=(p_shd, o_shd, b_shd),
                out_shardings=(p_shd, o_shd, None),
                donate_argnums=(0, 1),
            ).lower(params, opt, batch)
        else:
            qparams = sp.abstract_qparams(cfg, container)
            cache = sp.abstract_cache(cfg, shape)
            q_shd = shd.param_shardings(qparams, mesh)
            c_shd = shd.cache_shardings(cache, mesh)
            wvec, avec = sp.bit_vectors(cfg)
            rep = NamedSharding(mesh, P())
            if shape.kind == "prefill":
                batch = sp.input_specs(cfg, shape)
                b_shd = shd.batch_shardings(batch, mesh)

                def prefill_fn(q, b, c, wv, av):
                    return lm.prefill(q, b, cfg, wv, av, c)

                lowered = jax.jit(
                    prefill_fn,
                    in_shardings=(q_shd, b_shd, c_shd, rep, rep),
                    donate_argnums=(2,),
                ).lower(qparams, batch, cache, wvec, avec)
            else:
                toks = sp.input_specs(cfg, shape)
                tok_shd = NamedSharding(
                    mesh, shd.logical_to_mesh(
                        mesh, ("dp", None), toks["tok"].shape))

                def decode_fn(q, tok, t, c, wv, av):
                    return lm.decode_step(q, tok, t, c, cfg, wv, av)

                lowered = jax.jit(
                    decode_fn,
                    in_shardings=(q_shd, tok_shd, rep, c_shd, rep, rep),
                    donate_argnums=(3,),
                ).lower(qparams, toks["tok"], toks["t"], cache, wvec, avec)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = hloparse.cost_analysis_dict(compiled)
    hlo = compiled.as_text()
    walk = hloparse.summarize(hlo)          # trip-count-exact per-device cost
    colls = walk["collectives"]
    counts = param_counts(cfg)
    chips = 512 if multi_pod else 256

    flops_dev = walk["flops"]
    flops_i8_dev = walk["flops_int8"]
    bytes_dev = walk["bytes_opt"]       # ideal-fusion HBM floor (memory term)
    bytes_hlo = walk["bytes"]           # CPU-fused upper bound (reported)
    coll_dev = walk["collective_bytes"]
    mf = model_flops(cfg, shape, counts)

    peak_bytes = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "kind": shape.kind,
        "time_lower_s": round(t_lower, 2),
        "time_compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_bytes_per_device": int(peak_bytes),
            "fits_hbm_16g": bool(peak_bytes <= HBM_PER_CHIP),
        },
        "cost": {"flops_per_device": flops_dev,
                 "flops_int8_per_device": flops_i8_dev,
                 "bytes_per_device": bytes_dev,
                 "bytes_hlo_upper_bound": bytes_hlo,
                 "raw_cost_analysis_flops": float(ca.get("flops", 0.0)),
                 "raw_cost_analysis_bytes": float(
                     ca.get("bytes accessed", 0.0))},
        "collectives": colls,
        "collective_bytes_per_device": coll_dev,
        "model_flops_global": mf,
        "params": counts,
        "roofline": {
            "compute_s": (flops_dev / PEAK_FLOPS_BF16
                          + flops_i8_dev / (2 * PEAK_FLOPS_BF16)),
            "memory_s": bytes_dev / HBM_BW,
            "collective_s": coll_dev / (3 * ICI_BW),
            "model_flops_ratio": ((mf / chips)
                                  / max(flops_dev + flops_i8_dev, 1.0)),
        },
    }
    terms = result["roofline"]
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: terms[k])
    result["roofline"]["dominant"] = dom.replace("_s", "")
    return result


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every planned cell in subprocesses")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--container", default="int8", choices=("int8", "int4"))
    ap.add_argument("--kv-bits", type=int, default=0, choices=(0, 8),
                    help="int8 KV cache for serve cells (§Perf)")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    if args.all:
        cells = planned_cells()
        meshes = [False, True] if args.both_meshes else [bool(args.multi_pod)]
        failures = []
        for arch, shape in cells:
            for mp in meshes:
                tag = f"{arch}.{shape}.{'2x16x16' if mp else '16x16'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[skip] {tag} (exists)")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out", args.out,
                       "--container", args.container]
                if mp:
                    cmd.append("--multi-pod")
                print(f"[run ] {tag}", flush=True)
                r = subprocess.run(cmd, capture_output=True, text=True)
                if r.returncode != 0:
                    failures.append(tag)
                    print(f"[FAIL] {tag}\n{r.stdout[-2000:]}\n{r.stderr[-4000:]}")
                else:
                    print(r.stdout.strip().splitlines()[-1])
        print(f"\n{len(cells) * len(meshes) - len(failures)} ok, "
              f"{len(failures)} failed: {failures}")
        sys.exit(1 if failures else 0)

    res = lower_cell(args.arch, args.shape, args.multi_pod, args.container,
                     args.kv_bits)
    tag = f"{args.arch}.{args.shape}.{res['mesh']}"
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(res, f, indent=1)
    r = res["roofline"]
    print(f"[ok  ] {tag}: compile={res['time_compile_s']}s "
          f"peak={res['memory']['peak_bytes_per_device'] / 2**30:.2f}GiB "
          f"fits={res['memory']['fits_hbm_16g']} "
          f"compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
          f"collective={r['collective_s']:.4f}s dom={r['dominant']}")


if __name__ == "__main__":
    main()
