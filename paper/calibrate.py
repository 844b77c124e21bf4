"""The one-time calibration fit behind energy.py's CALIBRATED constants.

The paper's Table VI gives write energies but not per-cell compare/read
energy, the ReRAM sense-cycle slowdown, or the fraction of LUT-pass
writes that actually toggle a ReRAM cell.  This script fits those three
constants against the paper's own published numbers:

  targets (paper §V.A):
    * ReRAM/SRAM VGG16 energy ratios 80.9x @2b .. 63.1x @8b (Fig. 6)
    * ReRAM/SRAM latency ratio ~1.85x, flat in precision
    * absolute LR/SRAM ResNet50 energies 0.009 J @2b / 0.095 J @8b (Fig 7a)

Run it to regenerate the constants and their residuals; the values frozen
into `apsim/energy.py` come from exactly this fit (single fit — nothing
downstream re-tunes).  `python -m paper.calibrate`
"""
from __future__ import annotations

import dataclasses
import itertools

import numpy as np

from repro.apsim import energy as en
from repro.apsim.mapper import LR_CONFIG, simulate_network
from repro.apsim.workloads import resnet50, vgg16

PAPER_RATIOS = {2: 80.9, 3: 72.9, 4: 68.9, 5: 66.6, 6: 65.0, 7: 63.9,
                8: 63.1}
PAPER_RN50 = {2: 0.009, 8: 0.095}


def loss_for(e_cmp: float, toggle: float, sense: float) -> float:
    sram = dataclasses.replace(en.SRAM, e_compare_j=e_cmp, e_read_j=e_cmp)
    reram = dataclasses.replace(en.RERAM, e_compare_j=e_cmp, e_read_j=e_cmp,
                                lut_toggle_frac=toggle,
                                compare_cycles=sense, read_cycles=sense)
    v = vgg16()
    loss = 0.0
    for M, target in PAPER_RATIOS.items():
        rs = simulate_network(v, LR_CONFIG, sram, bits=M).energy_j
        rr = simulate_network(v, LR_CONFIG, reram, bits=M).energy_j
        loss += ((rr / rs - target) / target) ** 2
    r = resnet50()
    for M, target in PAPER_RN50.items():
        e = simulate_network(r, LR_CONFIG, sram, bits=M).energy_j
        loss += 4.0 * ((e - target) / target) ** 2
    return loss


def main() -> int:
    grid_cmp = np.geomspace(1e-14, 2e-13, 9)
    grid_tog = np.linspace(0.2, 0.8, 7)
    grid_sense = (1.5, 1.7, 2.0)
    best = min(itertools.product(grid_cmp, grid_tog, grid_sense),
               key=lambda t: loss_for(*t))
    # local refine around the winner
    c0, t0, s0 = best
    fine = min(itertools.product(np.linspace(0.6 * c0, 1.6 * c0, 11),
                                 np.linspace(max(0.2, t0 - 0.1),
                                             min(0.8, t0 + 0.1), 9),
                                 (s0,)),
               key=lambda t: loss_for(*t))
    c, t, s = fine
    print("calibrate: fitted constants (frozen into apsim/energy.py)")
    print(f"E_COMPARE_J,{c:.3e},frozen={en.E_COMPARE_J:.3e}")
    print(f"LUT_TOGGLE_FRAC_RERAM,{t:.3f},frozen={en.LUT_TOGGLE_FRAC_RERAM}")
    print(f"reram_sense_cycles,{s},frozen={en.RERAM.compare_cycles}")
    drift_c = abs(c - en.E_COMPARE_J) / en.E_COMPARE_J
    drift_t = abs(t - en.LUT_TOGGLE_FRAC_RERAM)
    print(f"check,refit_within_15pct_of_frozen,{drift_c < 0.15 and drift_t < 0.1}")
    return 0 if (drift_c < 0.15 and drift_t < 0.1) else 1


if __name__ == "__main__":
    raise SystemExit(main())
