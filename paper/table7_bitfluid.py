"""Table VII — bit fluidity: HAWQ-V3 ResNet18 mixed-precision configs on
BF-IMNA (LR), normalized energy/latency + EDP vs fixed INT4/INT8.

Two halves, one table: the analytic AP simulator prices each config's
energy/latency/EDP, and the serve-form CNN path runs every config through
the REAL kernel dispatch layer (ops.serve_linear, int8 containers) in one
compiled program — fidelity vs the fp reference supplies the accuracy
axis of the accuracy-vs-EDP trade-off functionally, with trace-count == 1
across all five configuration switches (the zero-retrace claim).

Accuracy and model size columns are adopted from HAWQ-V3 [53] (inputs to
the trade-off, not simulator outputs — same as the paper)."""
from __future__ import annotations

from repro.apsim.energy import SRAM
from repro.apsim.mapper import LR_CONFIG, simulate_network
from repro.apsim.workloads import (HAWQV3_METADATA, HAWQV3_RESNET18,
                                   per_layer_bits, resnet18)

PAPER = {  # constraint: (norm_energy, norm_latency, edp)
    "int4": (3.29, 1.004, 0.58),
    "high": (1.13, 1.001, 1.69),
    "medium": (1.22, 1.002, 1.56),
    "low": (1.90, 1.004, 1.00),
    "int8": (1.0, 1.0, 1.91),
}

def run():
    layers = resnet18()
    reports = {}
    for name, vec in HAWQV3_RESNET18.items():
        bits = per_layer_bits(layers, vec)
        reports[name] = simulate_network(layers, LR_CONFIG, SRAM, bits=bits,
                                         network="resnet18")
    return reports


def main(full: bool = True) -> int:
    reports = run()
    base = reports["int8"]
    # paper normalizes energy so that INT4 consumes less absolute energy
    # but reports >1 normalized energy due to its fixed-latency basis; we
    # report our simulator's direct normalization and the paper's values.
    from repro.serve.cnn import hawq_fidelity_sweep

    # smoke shrinks the serve-fidelity image (same program structure,
    # same trace-count gate, lighter compile); --full keeps paper depth
    fid, n_traces = hawq_fidelity_sweep(image=32 if full else 16)
    print("table7: HAWQ-V3 ResNet18 on BF-IMNA (LR/SRAM) + serve kernels")
    print("constraint,avg_bits,norm_energy,norm_latency,edp_rel,"
          "paper_edp_ordering,serve_fidelity,size_mb,top1")
    edps = {}
    ok = True
    for name in ("int4", "low", "medium", "high", "int8"):
        r = reports[name]
        vec = HAWQV3_RESNET18[name]
        bits = per_layer_bits(resnet18(), vec)
        avg = sum(bits) / len(bits)
        ne = r.energy_j / base.energy_j
        nl = r.latency_s / base.latency_s
        edps[name] = r.edp
        meta = HAWQV3_METADATA[name]
        print(f"{name},{avg:.2f},{ne:.3f},{nl:.4f},"
              f"{r.edp / base.edp:.3f},{PAPER[name][2]},{fid[name]:.4f},"
              f"{meta['size_mb']},{meta['top1']}")
    # ordering claims of the paper's Table VII:
    #  * INT4 best EDP; among mixed configs low < medium < high EDP;
    #  * all mixed EDPs beat INT8;
    #  * latency ~constant (within 2%) across configs (bit-serial cols);
    #  * run FUNCTIONALLY: every config through one compiled serve-form
    #    program (zero retrace), higher-bit ends more faithful to fp.
    ok &= edps["int4"] < edps["low"] < edps["medium"] < edps["high"]
    ok &= edps["high"] < edps["int8"]
    lat_spread = (max(r.latency_s for r in reports.values())
                  / min(r.latency_s for r in reports.values()))
    ok &= lat_spread < 1.10
    ok &= n_traces == 1
    ok &= fid["int8"] > fid["int4"]
    print(f"check,edp_ordering_int4<low<med<high<int8,{ok}")
    print(f"check,latency_spread,{lat_spread:.3f}")
    print(f"check,serve_traces,{n_traces}")
    print(f"check,fidelity_int8>{fid['int8']:.4f}>int4>{fid['int4']:.4f}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
