"""Static-analysis suite tests (DESIGN.md §12).

Every lint rule is pinned twice: a fixture snippet that MUST fire (true
positive) and a near-miss that must NOT (documented false-positive
guard — e.g. ``float()`` on a host-side numpy value is fine).  The
retrace auditor gets signature snapshot tests plus a deliberate
host-conversion bug it must catch; the sharding checker and ledger
auditor get synthetic violations; and the repo itself must audit clean
— the same gate CI blocks on.
"""
import ast
import json
import textwrap

import numpy as np
import pytest

from repro.analysis import (ALL_PASSES, Baseline, common, ledger, lint,
                            registry, retrace, run_suite, sharding)

# ---------------------------------------------------------------------------
# lint fixtures
# ---------------------------------------------------------------------------

KERNEL = "src/repro/kernels/fixture.py"      # whole-module hot ("*")
ENGINE = "src/repro/serve/engine.py"         # hot only in registered scopes


def _mod(src: str, relpath: str = KERNEL) -> common.ParsedModule:
    src = textwrap.dedent(src)
    return common.ParsedModule(relpath=relpath, source=src,
                               tree=ast.parse(src),
                               lines=src.splitlines())


def _rules(src: str, relpath: str = KERNEL):
    return [f.rule for f in lint.lint_modules([_mod(src, relpath)])]


def test_hs101_item_on_device_fires():
    src = """
    import jax.numpy as jnp
    def f():
        x = jnp.zeros(3)
        return x.item()
    """
    assert "HS101" in _rules(src)


def test_hs101_item_on_host_numpy_does_not_fire():
    src = """
    import numpy as np
    def f():
        a = np.zeros(3)
        return a.item()
    """
    assert _rules(src) == []


def test_hs102_float_on_device_fires():
    src = """
    import jax.numpy as jnp
    def f(x):
        return float(jnp.sum(x))
    """
    assert "HS102" in _rules(src)


def test_hs102_float_on_host_numpy_does_not_fire():
    # the documented false-positive guard: host-side numpy math is free
    src = """
    import numpy as np
    def f():
        a = np.arange(4)
        return float(np.mean(a))
    """
    assert _rules(src) == []


def test_hs102_asarray_on_device_fires():
    src = """
    import numpy as np
    import jax.numpy as jnp
    def f(x):
        y = jnp.exp(x)
        return np.asarray(y)
    """
    assert "HS102" in _rules(src)


def test_hs102_device_get_clears_taint():
    # the sanctioned coalesced transfer: everything downstream is host
    src = """
    import jax
    import jax.numpy as jnp
    def f(x):
        y = jnp.exp(x)
        h = jax.device_get(y)
        return float(h[0])
    """
    assert _rules(src) == []


def test_hs102_pricer_on_device_args_fires():
    src = """
    import jax.numpy as jnp
    class ServeEngine:
        def _decode_tick(self, budgets):
            wv, av = self.controller.resolve(budgets)
            return self.price_bits(wv, av)
    """
    assert "HS102" in _rules(src, ENGINE)


def test_hs102_pricer_on_host_bits_does_not_fire():
    src = """
    class ServeEngine:
        def _decode_tick(self, budget):
            wv, av = self.host_bits(budget)
            return self.price_bits(wv, av)
    """
    assert _rules(src, ENGINE) == []


def test_hs102_only_fires_in_hot_scopes():
    # same sync, but in an unregistered method: setup-time syncs are fine
    src = """
    import jax.numpy as jnp
    class ServeEngine:
        def build_tables(self, budgets):
            wv, av = self.controller.resolve(budgets)
            return self.price_bits(wv, av)
    """
    assert _rules(src, ENGINE) == []


def test_hs103_branch_on_device_fires():
    src = """
    import jax.numpy as jnp
    def f(x):
        if jnp.any(x > 0):
            return 1
        return 0
    """
    assert "HS103" in _rules(src)


def test_hs103_branch_on_host_flag_does_not_fire():
    src = """
    def f(flag):
        if flag:
            return 1
        return 0
    """
    assert _rules(src) == []


def test_nd201_set_iteration_fires():
    src = """
    def f():
        out = []
        for k in {2, 1, 3}:
            out.append(k)
        return out
    """
    assert "ND201" in _rules(src)


def test_nd201_sorted_set_does_not_fire():
    src = """
    def f(vals):
        return [k for k in sorted({v for v in vals})]
    """
    assert _rules(src) == []


def test_rng301_unseeded_rng_fires():
    src = """
    import numpy as np
    def f():
        return np.random.default_rng().normal()
    """
    assert "RNG301" in _rules(src)


def test_rng301_seeded_rng_does_not_fire():
    src = """
    import numpy as np
    def f(seed):
        return np.random.default_rng(seed).normal()
    """
    assert _rules(src) == []


def test_stat401_static_bit_argnames_fires():
    src = """
    import jax
    def build():
        def fwd(x, wbits):
            return x * wbits
        return jax.jit(fwd, static_argnames=("wbits",))
    """
    assert "STAT401" in _rules(src)


def test_stat401_captured_bit_local_fires():
    src = """
    import jax
    def build(wv):
        def fwd(x):
            return x * wv
        return jax.jit(fwd)
    """
    assert "STAT401" in _rules(src)


def test_stat401_static_tiling_params_do_not_fire():
    # tiling/block-shape statics are the sanctioned use of static_argnames
    src = """
    import jax
    def build():
        def fwd(x, bm, bn):
            return x
        return jax.jit(fwd, static_argnames=("bm", "bn"))
    """
    assert _rules(src) == []


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------

def test_baseline_suppresses_and_goes_stale():
    f = common.Finding(rule="HS102", file="src/x.py", line=3, scope="f",
                       message="sync", snippet="float(y)")
    bl = Baseline([{"rule": "HS102", "file": "src/x.py",
                    "match": "float(y)", "why": "justified"}])
    fresh, suppressed = common.apply_baseline([f], bl)
    assert fresh == [] and len(suppressed) == 1 and bl.stale() == []
    unused = Baseline([{"rule": "HS101", "file": "gone.py",
                        "match": "x.item()", "why": "old"}])
    assert len(unused.stale()) == 1


def test_baseline_entry_requires_why():
    with pytest.raises(ValueError):
        Baseline([{"rule": "HS102", "file": "x.py", "match": "y"}])


def test_checked_in_baseline_is_small_and_justified():
    with open(common.BASELINE_PATH) as f:
        entries = json.load(f)["entries"]
    assert len(entries) <= 5
    assert all(e.get("why") for e in entries)


# ---------------------------------------------------------------------------
# retrace auditor
# ---------------------------------------------------------------------------

def test_signature_is_deterministic_and_shape_sensitive():
    import jax.numpy as jnp

    def fn(x):
        return jnp.sum(x * 2)

    a = jnp.zeros((4,))
    assert retrace.signature(fn, a) == retrace.signature(fn, a)
    assert retrace.signature(fn, a) != retrace.signature(
        fn, jnp.zeros((8,)))


def test_audit_entrypoint_flags_host_conversion_rt502():
    import jax.numpy as jnp

    def buggy(x):
        return jnp.asarray(int(np.asarray(x).max()))   # host round-trip

    rep = retrace.audit_entrypoint(
        "fix", "buggy", [("v0", lambda: (jnp.zeros((2,)),))], buggy)
    assert not rep.ok
    assert [f.rule for f in rep.findings()] == ["RT502"]


def test_audit_entrypoint_flags_multi_signature_rt501():
    import jax.numpy as jnp

    def fn(x):
        return x + 1

    rep = retrace.audit_entrypoint(
        "fix", "drift",
        [("v0", lambda: (jnp.zeros((2,)),)),
         ("v1", lambda: (jnp.zeros((3,)),))],    # shape leaks into jaxpr
        fn)
    assert len(rep.signatures) == 2
    assert [f.rule for f in rep.findings()] == ["RT501"]


def test_retrace_one_config_single_signature_snapshot():
    # the full ten-config × CNN sweep runs in the CI analysis job; one
    # dense config here pins the auditor end to end (6 entrypoints:
    # prefill_row, decode_scan, sample_first, extend_row, draft_scan,
    # verify_chunk)
    reports = retrace.audit_config("qwen3_4b")
    assert {r.entrypoint for r in reports} >= {
        "prefill_row", "decode_scan", "sample_first", "extend_row"}
    for r in reports:
        assert r.ok, (r.entrypoint, r.signatures, r.errors)
        assert len(r.signatures) == 1


# ---------------------------------------------------------------------------
# sharding checker
# ---------------------------------------------------------------------------

def test_check_resolved_catches_bad_arithmetic():
    from jax.sharding import PartitionSpec as P
    mesh = sharding.FakeMesh((("data", 2), ("model", 2)))
    # non-dividing dim
    bad = sharding.check_resolved(P("model"), (5,), mesh, "w")
    assert [f.rule for f in bad] == ["SH601"]
    # axis consumed twice
    dup = sharding.check_resolved(P("data", "data"), (4, 4), mesh, "w")
    assert any("two dims" in f.message for f in dup)
    # unknown axis
    unk = sharding.check_resolved(P("pod"), (4,), mesh, "w")
    assert any("not in mesh" in f.message for f in unk)
    # clean spec
    assert sharding.check_resolved(P("data", "model"), (4, 6), mesh,
                                   "w") == []


def test_dropped_axes_reports_fallback_but_not_singletons():
    mesh = sharding.FakeMesh((("data", 2), ("model", 2)))
    # 5 % 2 != 0: requested 'tp' placement silently replicated
    assert sharding.dropped_axes(mesh, ("tp", "dp"), (5, 4)) == [
        (0, "tp", 2)]
    # singleton dims replicate by design — no report
    assert sharding.dropped_axes(mesh, ("tp", "dp"), (1, 4)) == []


def test_sharding_one_config_clean():
    meshes = [sharding.FakeMesh((("data", 2), ("model", 2)))]
    findings, stats = sharding.audit_config_sharding("qwen3_4b", meshes)
    assert findings == [], [f.render() for f in findings]
    assert stats["sharded"] > 0


# ---------------------------------------------------------------------------
# ledger auditor
# ---------------------------------------------------------------------------

FAKE_ACCT = """
import dataclasses

@dataclasses.dataclass
class CostRecord:
    rid: int
    used: float = 0.0
    orphan: float = 0.0
    base: float = 0.0

    @property
    def derived(self):
        return self.base * 2

def aggregate(records):
    return {"used": sum(r.used for r in records),
            "derived": sum(r.derived for r in records)}
"""

FAKE_SERVE = """
def admit(record, CostRecord):
    record.used = 1.0
    record.orphan = 2.0
    r = CostRecord(rid=0, base=3.0)
    return r
"""


def test_ledger_transitive_consumption_and_orphan():
    acct = _mod(FAKE_ACCT, "src/repro/serve/accounting.py")
    fields, members = ledger.record_schema(acct)
    assert fields == {"rid", "used", "orphan", "base"}
    consumed = ledger.consumed_fields(acct, fields, members)
    assert consumed == {"used", "base"}       # base via derived property
    writes = ledger.written_fields(
        [_mod(FAKE_SERVE, "src/repro/serve/fake.py")], fields)
    assert set(writes) == {"used", "orphan", "rid", "base"}


def test_ledger_repo_is_clean():
    findings, detail = ledger.run_ledger()
    assert findings == [], [f.render() for f in findings]
    # every written field is consumed or deliberately waived
    waived = set(registry.LEDGER_WAIVED)
    assert detail["written"] <= (detail["consumed"] | waived)


# ---------------------------------------------------------------------------
# suite + CLI
# ---------------------------------------------------------------------------

def test_repo_lint_is_clean():
    assert lint.run_lint(common.repo_root()) == []


def test_run_suite_fast_passes_ok():
    res = run_suite(passes=("lint", "ledger"))
    assert res.ok
    d = res.to_dict()
    assert d["ok"] and set(d["passes"]) == {"lint", "ledger"}


def test_cli_exit_codes(tmp_path):
    from repro.launch import analyze
    out = tmp_path / "status.json"
    assert analyze.main(["--lint", "--ledger",
                         "--json", str(out)]) == 0
    payload = json.loads(out.read_text())
    assert payload["ok"] is True
    assert set(ALL_PASSES) == {"lint", "retrace", "sharding", "ledger"}
