"""The paper's tables and figures, reproduced on the AP simulator.

Each module under ``paper/`` prints one table or figure of the paper and
returns non-zero from ``main()`` when a claim it checks fails: Fig. 6's
30% error bound, Table VIII's peak-throughput claims, the calibration
residuals.  These are AP-model numbers, not device speed.

``table7_bitfluid`` is left out: its serve-fidelity sweep compiles
ResNet18 and takes ~30 s on a CPU.  Its one-program serve half is held
by ``test_cnn_serve.py::test_hawq_resnet18_one_compiled_program``; run
the whole table with ``PYTHONPATH=src python paper/table7_bitfluid.py``.
"""
import importlib.util
import inspect
import os

import pytest

from repro.analysis import common

PAPER_MODULES = ("calibrate", "fig5_runtimes", "fig6_technology", "fig7_dse",
                 "fig8_breakdown", "table8_sota")


def _load(name):
    path = os.path.join(common.repo_root(), "paper", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"paper_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", PAPER_MODULES)
def test_paper_module_claims_hold(name):
    main = _load(name).main
    kw = {"full": False} if "full" in inspect.signature(main).parameters \
        else {}
    assert main(**kw) == 0
