"""Registries shared by the analysis passes (DESIGN.md §12).

Three kinds of project knowledge live here, OUT of the generic pass
machinery, so growing the codebase means editing data, not analyzers:

* **Hot scopes** — the per-tick / per-admission serving paths where a
  host sync is a real throughput bug.  One-time setup (``__init__``,
  pool construction) and cached host-side helpers (``_host_index``,
  ``host_bits``, ``_config_cost`` — the sanctioned per-admission
  mirrors) are deliberately NOT registered: syncing once at
  construction is fine, and the caching helpers exist precisely so the
  hot paths don't have to.
* **Taint vocabulary** — which callees produce device values, which
  produce host values, and which force a sync on whatever they're
  given.  The linter's dataflow is intraprocedural; these sets are its
  interprocedural knowledge.
* **Ledger waivers** — ``CostRecord`` fields written by the serve
  layer that ``accounting.aggregate()`` intentionally does not read,
  each naming its real consumer.
"""
from __future__ import annotations

import fnmatch
from typing import Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# Hot scopes for the host-sync rules (HS101/HS102/HS103)
# ---------------------------------------------------------------------------
# file pattern (repo-relative, fnmatch) -> qualname patterns.  "*" marks
# a whole module hot (kernels execute inside traces; any sync there is
# wrong at any time).
HOT_SCOPES: Dict[str, Tuple[str, ...]] = {
    "src/repro/serve/engine.py": (
        "ServeEngine._admit",
        "ServeEngine._admit_request",
        "ServeEngine._step",
        "ServeEngine._decode_tick",
        "ServeEngine._spec_round",
        "ServeEngine._batch_bits",
        "ServeEngine._generate",
        "ServeEngine._finish",
    ),
    "src/repro/serve/runtime.py": (
        "ServeRuntime.admit_record",
        "ServeRuntime.plan_admissions",
        "ServeRuntime.charge",
        "ServeRuntime.new_record",
        "ServeRuntime.next_admission",
        "ServeRuntime.finish_record",
        "ServeRuntime.sched_tick",
        "ServeRuntime.age_queue",
    ),
    "src/repro/serve/cnn.py": (
        "CNNServeEngine.serve",
    ),
    "src/repro/kernels/*.py": ("*",),
}


def hot_patterns(relpath: str) -> Tuple[str, ...]:
    """Qualname patterns registered hot for one file ('' when none)."""
    out: Tuple[str, ...] = ()
    for pat, quals in HOT_SCOPES.items():
        if fnmatch.fnmatch(relpath, pat):
            out += quals
    return out


def is_hot(relpath: str, qualname: str) -> bool:
    for pat in hot_patterns(relpath):
        if pat == "*" or fnmatch.fnmatch(qualname, pat):
            return True
        # nested defs inherit their enclosing scope's hotness
        if qualname.startswith(pat + ".") or qualname.startswith(
                pat + ".<locals>."):
            return True
    return False


# ---------------------------------------------------------------------------
# Taint vocabulary for the host-sync dataflow
# ---------------------------------------------------------------------------

# method/attribute names whose call RETURNS device values (jax arrays):
# seeds of the taint.  Matched on the final attribute of the callee.
DEVICE_METHODS = frozenset({
    # controller / sharding
    "resolve", "shard_bits", "shard_budgets", "shard_batch", "device_put",
    # ServeEngine compiled programs + helpers
    "_prefill", "_prefill_row", "_decode_scan", "_decode_scan_sh",
    "_draft", "_verify", "_sample_first", "_extend_row",
    "_bits", "_batch_bits", "_draft_bits", "_split_key",
    # CNN compiled program
    "_fwd",
})

# names whose call returns HOST values even when fed device state — the
# sanctioned cached per-admission helpers plus the coalesced transfer.
HOST_METHODS = frozenset({
    "host_bits", "_host_index", "_config_cost", "device_get",
    "block_until_ready",        # returns its (still-device) arg; callers
                                # using it as a barrier are not syncing data
})

# callees that force a host sync of their *arguments*: calling them on a
# device value is itself the finding (they np.asarray internally).
SYNC_ARG_METHODS = frozenset({
    "price_bits", "price", "price_verify", "price_matrix",
})

# jax.* callees that do NOT produce device values (abstract eval, host
# transfer, specs) — exempt from the jnp/jax taint seeding.
JAX_HOST_CALLS = frozenset({
    "jax.device_get", "jax.eval_shape", "jax.make_jaxpr",
    "jax.ShapeDtypeStruct", "jax.tree_util.tree_structure",
    "jax.block_until_ready",
})


# ---------------------------------------------------------------------------
# Closure-capture audit (STAT401)
# ---------------------------------------------------------------------------
# A captured local matching this predicate inside a jitted closure is a
# bit width baked in at trace time — the paper's §V.B invariant (one
# program across all precisions) dies exactly this way.
BIT_NAMES = frozenset({"wv", "av", "wb", "ab", "wmat", "amat"})


def is_bit_name(name: str) -> bool:
    return name in BIT_NAMES or "bit" in name.lower()


# ---------------------------------------------------------------------------
# Ledger waivers (ledger auditor)
# ---------------------------------------------------------------------------
# CostRecord fields written in serve/ that aggregate() intentionally
# does not consume, each naming the real consumer.  An aggregate()-side
# pickup makes the waiver STALE (the auditor flags it for removal).
LEDGER_WAIVED: Dict[str, str] = {
    "rid": "request identity joining the runtime queue, engine slots, "
           "and per-request report tables",
    "submitted_s": "latency_s property -> wall-clock latency reporting",
    "budget_s": "per-request SLO attainment in traffic.Collector and "
                "launch/serve.py's per-request table",
    "mean_wbits": "traffic.Collector bits-per-window series and the "
                  "launch CLIs' per-request tables",
    "cached_mean_wbits": "prefix-cache precision introspection in "
                         "launch/serve.py --prefix-cache ledger",
    "cached_cost": "hit repricing vs miss pricing in "
                   "tests/test_prefix_cache.py",
    "cache_hit": "hit-kind assertions in tests/test_prefix_cache.py",
    "planned_units": "axis_planned() admission charge, reconciled in "
                     "ServeRuntime.finish_record",
    "slot": "slot lifecycle bookkeeping in ServeEngine._admit/_finish",
    "submitted_tick": "queue-delay series in traffic.Collector",
    "admitted_tick": "queue-delay series in traffic.Collector",
    "finished_tick": "latency_ticks property -> traffic.Collector "
                     "tick-domain latency percentiles",
    "finished_s": "latency_s property -> wall-clock latency reporting",
    "spec_k": "axis_planned() draft charge and the per-request unit "
              "checks in tests/test_spec_decode.py",
    "planned_spec_rounds": "axis_planned() speculative charge, "
                           "reconciled in finish_record",
    "planned_spec_tokens": "axis_planned() speculative charge, "
                           "reconciled in finish_record",
    # ImageStats-only fields (CNN serve writes them through the same
    # record type family)
    "index": "batch-position bookkeeping in CNNServeEngine.serve",
    "wbits": "per-image config introspection (chip_smoke.py's CNN "
             "phase, bench/kinds/cnn.py)",
    "abits": "per-image config introspection (chip_smoke.py's CNN "
             "phase, bench/kinds/cnn.py)",
}


def waiver_for(field: str) -> Optional[str]:
    return LEDGER_WAIVED.get(field)
