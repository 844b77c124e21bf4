"""Fig. 6 — ReRAM/SRAM energy and latency ratios, VGG16, precisions 2..8.

Paper targets: energy ratio falls 80.9x -> 63.1x as precision rises 2->8;
latency ratio ~1.85x flat.  Constants not in Table VI were CALIBRATED once
(energy.py); this benchmark reports predicted vs paper ratios."""
from __future__ import annotations

from repro.apsim.energy import RERAM, SRAM
from repro.apsim.mapper import LR_CONFIG, simulate_network
from repro.apsim.workloads import vgg16

PAPER_ENERGY_RATIOS = {2: 80.9, 3: 72.9, 4: 68.9, 5: 66.6, 6: 65.0,
                       7: 63.9, 8: 63.1}
PAPER_LATENCY_RATIO = 1.85


def main() -> int:
    layers = vgg16()
    print("fig6: ReRAM/SRAM ratios, VGG16, LR config")
    print("precision,energy_ratio,paper_energy_ratio,latency_ratio,"
          "paper_latency_ratio")
    worst = 0.0
    for M in range(2, 9):
        rs = simulate_network(layers, LR_CONFIG, SRAM, bits=M,
                              network="vgg16")
        rr = simulate_network(layers, LR_CONFIG, RERAM, bits=M,
                              network="vgg16")
        er = rr.energy_j / rs.energy_j
        lr = rr.latency_s / rs.latency_s
        pe = PAPER_ENERGY_RATIOS[M]
        worst = max(worst, abs(er - pe) / pe)
        print(f"{M},{er:.1f},{pe},{lr:.2f},{PAPER_LATENCY_RATIO}")
    trend_ok = True
    prev = None
    for M in range(2, 9):
        rs = simulate_network(layers, LR_CONFIG, SRAM, bits=M).energy_j
        rr = simulate_network(layers, LR_CONFIG, RERAM, bits=M).energy_j
        r = rr / rs
        if prev is not None and r > prev + 1e-6:
            trend_ok = False
        prev = r
    vs_ok = voltage_scaling_check()
    print(f"check,energy_ratio_max_rel_err,{worst:.3f}")
    print(f"check,ratio_monotone_decreasing,{trend_ok}")
    print(f"check,voltage_scaling_insignificant,{vs_ok}")
    return 0 if (worst < 0.30 and trend_ok and vs_ok) else 1


def voltage_scaling_check() -> bool:
    """Paper §V.A: scaling SRAM VDD 1.0 -> 0.5 V (write energy 0.24 ->
    0.06 fJ) saves < 0.1% end-to-end — compares dominate once writes are
    sub-fJ."""
    from repro.apsim.energy import voltage_scaled
    layers = vgg16()
    base = simulate_network(layers, LR_CONFIG, SRAM, bits=8).energy_j
    scaled_tech = voltage_scaled(SRAM, 0.5)
    scaled = simulate_network(layers, LR_CONFIG, scaled_tech, bits=8).energy_j
    saving = (base - scaled) / base
    print(f"voltage_scaling,energy_saving_frac,{saving:.5f},"
          f"err_prob,{scaled_tech.write_error_prob}")
    return 0.0 <= saving < 0.005


if __name__ == "__main__":
    raise SystemExit(main())
