"""Retrace auditor (DESIGN.md §12, pass 2 of 4): the *static* proof of
the zero-retrace invariant.

``RuntimeStats.trace()`` counters prove at RUN time that one compiled
program serves every precision configuration (paper §V.B).  This pass
proves it at ANALYSIS time: every registered entrypoint — ragged
prefill, the scan-fused decode block, the SPEC_K_MAX draft scan, the
chunked verify, ``_extend_row``, and the CNN conv-GEMM forward — is
abstractly evaluated with :func:`jax.make_jaxpr` over real
:class:`~repro.serve.engine.ServeEngine` instances built on
``jax.eval_shape``'d parameters (no weight allocation, so the 1T-param
configs audit in milliseconds), across a variant matrix of budgets ×
draft depth k × (start, length).

Two failure modes, both fatal:

* **RT501** — an entrypoint yields more than one abstract signature
  (sha256 of input avals + jaxpr text) across its variants: some
  variant-dependent value reached the program as a static (weak-dtype
  drift, a Python scalar that shapes the jaxpr, a baked-in literal).
* **RT502** — an entrypoint fails to trace abstractly: budgets, bit
  vectors, and k/start/length enter the wrapper as TRACED inputs (the
  wrapper runs ``controller.resolve`` inside the trace), so any host
  conversion on the budget→bits→program path — ``int()`` on a bit
  width, ``np.asarray`` on a traced vector — raises
  ConcretizationTypeError right here instead of a retrace in
  production.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.analysis.common import Finding

# budget values spanning the default controller's config table
BUDGETS = (0.0, 0.6, 0.8, 2.0)
BUDGET_MIXES = ((0.0, 2.0), (0.6, 0.8), (2.0, 2.0), (0.8, 0.0))

# audit-engine geometry (smoke configs: L=2, d=64, V=512)
N_SLOTS = 2
PREFILL_LEN = 8
MAX_LEN = 48
DECODE_BLOCK = 4

ENTRYPOINT_FILES: Dict[str, str] = {
    "prefill": "src/repro/models/lm.py",
    "decode_step": "src/repro/models/lm.py",
    "prefill_row": "src/repro/serve/engine.py",
    "decode_scan": "src/repro/serve/engine.py",
    "draft_scan": "src/repro/serve/engine.py",
    "verify_chunk": "src/repro/serve/engine.py",
    "extend_row": "src/repro/serve/engine.py",
    "sample_first": "src/repro/serve/engine.py",
    "cnn_forward": "src/repro/models/cnn.py",
}


@dataclasses.dataclass
class TraceReport:
    """One (config, entrypoint) audit: variant labels per signature."""
    config: str
    entrypoint: str
    signatures: Dict[str, List[str]]     # sig hash -> variant labels
    errors: Dict[str, str]               # variant label -> error text

    @property
    def ok(self) -> bool:
        return len(self.signatures) == 1 and not self.errors

    def findings(self) -> List[Finding]:
        file = ENTRYPOINT_FILES.get(self.entrypoint, "")
        out: List[Finding] = []
        if len(self.signatures) > 1:
            parts = "; ".join(
                f"{sig}: {', '.join(labels)}"
                for sig, labels in sorted(self.signatures.items()))
            out.append(Finding(
                rule="RT501", file=file, line=0,
                scope=f"{self.config}.{self.entrypoint}",
                message=f"{len(self.signatures)} abstract signatures "
                        f"across {sum(map(len, self.signatures.values()))} "
                        f"variants ({parts}) — each will compile "
                        f"separately in production",
                hint="a variant-dependent value is reaching the program "
                     "as a static; keep budgets/bits/k/start/length "
                     "traced (jnp.asarray) end to end"))
        for label, err in sorted(self.errors.items()):
            out.append(Finding(
                rule="RT502", file=file, line=0,
                scope=f"{self.config}.{self.entrypoint}",
                message=f"variant {label!r} failed abstract trace: {err}",
                hint="a host conversion (int()/float()/np.asarray) sits "
                     "on the budget->bits->program path; keep it traced"))
        return out


def signature(fn: Callable, *args) -> str:
    """sha256 of (input avals, jaxpr text) — the abstract identity of
    the program XLA would compile for these arguments."""
    import jax
    closed = jax.make_jaxpr(fn)(*args)
    avals = ", ".join(str(v.aval) for v in closed.jaxpr.invars)
    text = avals + "\n" + str(closed)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def audit_entrypoint(config: str, entrypoint: str,
                     variants: Sequence[Tuple[str, Callable[[], Tuple]]],
                     fn: Callable) -> TraceReport:
    """Trace ``fn`` once per variant (each thunk builds the arg tuple
    through the same construction code the engine uses) and bucket the
    resulting signatures."""
    sigs: Dict[str, List[str]] = {}
    errors: Dict[str, str] = {}
    for label, thunk in variants:
        try:
            sig = signature(fn, *thunk())
        except Exception as e:                  # noqa: BLE001 - reported
            errors[label] = f"{type(e).__name__}: {e}".splitlines()[0][:200]
            continue
        sigs.setdefault(sig, []).append(label)
    return TraceReport(config=config, entrypoint=entrypoint,
                       signatures=sigs, errors=errors)


# ---------------------------------------------------------------------------
# Abstract model state (no weight allocation)
# ---------------------------------------------------------------------------

def abstract_qparams(cfg):
    """ShapeDtypeStruct pytree of the serve-form quantized params."""
    import jax
    from repro.models import lm

    params = jax.eval_shape(lambda k: lm.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    return jax.eval_shape(lambda p: lm.quantize_params(p, cfg), params)


def abstract_cache(cfg, batch: int, max_len: int):
    import jax
    from repro.models import lm
    return jax.eval_shape(lambda: lm.empty_cache(cfg, batch, max_len))


def _default_controller(n: int):
    from repro.launch.serve import default_controller
    return default_controller(n)


# ---------------------------------------------------------------------------
# Per-config audits
# ---------------------------------------------------------------------------

def _build_engine(cfg):
    from repro import dist
    from repro.models import lm
    from repro.serve.engine import ServeEngine

    spec_ok = (cfg.family in lm.SPEC_CHUNK_FAMILIES
               and not cfg.sliding_window)
    controller = _default_controller(lm.n_bit_slots(cfg))
    # audit WITH placement enabled: an 8-device fully-replicated plan
    # attached to the engine must not change any compiled program's
    # signature (plans amortize host-side pricing; they never enter a
    # jaxpr)
    plan = dist.plan_for_controller(
        controller, lm.layer_gemm_dims(cfg), n_devices=8,
        head=lm.head_gemm_dims(cfg))
    return ServeEngine(
        cfg, abstract_qparams(cfg), max_len=MAX_LEN,
        controller=controller, plan=plan,
        n_slots=N_SLOTS, prefill_len=PREFILL_LEN,
        decode_block=DECODE_BLOCK,
        spec_k=2 if spec_ok else None,
        draft_budget_s=0.0 if spec_ok else None)


def _audit_engine(name: str, cfg) -> List[TraceReport]:
    """Engine-level audit for the continuous-batching families: the
    compiled programs, reached through the engine's own argument
    construction, with resolve() inside the trace."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    from repro.serve.engine import SPEC_K_MAX

    eng = _build_engine(cfg)
    B, V = N_SLOTS, cfg.vocab_size
    cache = abstract_cache(cfg, B, MAX_LEN)
    row = abstract_cache(cfg, 1, MAX_LEN)
    q = eng.qparams
    npre = cfg.n_prefix_tokens if cfg.family == "vlm" else 0
    reports: List[TraceReport] = []

    def keys(n: int):
        return jax.random.split(jax.random.PRNGKey(0), n)

    def slot_f32(vals):
        import numpy as np
        return jnp.asarray(np.asarray(vals, np.float64), jnp.float32)

    # ---- prefill_row: per-admission ragged prefill -------------------
    def prefill_row_fn(qp, budget, tokens, length, *prefix):
        wv, av = eng.controller.resolve(budget)
        return eng._prefill_row(qp, tokens, length, wv, av, *prefix)

    def prefill_row_args(budget: float, S: int):
        tokens = jnp.zeros((1, PREFILL_LEN), jnp.int32)
        extra = (() if npre == 0
                 else (jax.ShapeDtypeStruct((1, npre, cfg.d_model),
                                            jnp.float32),))
        return (q, jnp.asarray(budget, jnp.float32), tokens,
                jnp.asarray([S], jnp.int32)) + extra

    reports.append(audit_entrypoint(
        name, "prefill_row",
        [(f"budget={b}/S={s}",
          lambda b=b, s=s: prefill_row_args(b, s))
         for b in BUDGETS[:3] for s in (1, PREFILL_LEN)],
        prefill_row_fn))

    # ---- decode_scan: the per-tick scan-fused block ------------------
    def decode_fn(qp, budgets, tok, t, cache, temp, topk, ks):
        wv, av = eng.controller.resolve(budgets)
        return eng._decode_scan(qp, tok, t, cache, wv, av, temp, topk, ks)

    def decode_args(mix):
        return (q, slot_f32(mix),
                jnp.zeros((B, 1), jnp.int32),
                jnp.zeros((B,), jnp.int32), cache,
                jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
                keys(DECODE_BLOCK))

    reports.append(audit_entrypoint(
        name, "decode_scan",
        [(f"mix={mix}", lambda mix=mix: decode_args(mix))
         for mix in BUDGET_MIXES],
        decode_fn))

    # ---- sample_first: per-admission first-token sampling ------------
    reports.append(audit_entrypoint(
        name, "sample_first",
        [(f"temp={temp}", lambda temp=temp: (
            jax.ShapeDtypeStruct((1, 1, V), jnp.float32), keys(1)[0],
            jnp.asarray([temp], jnp.float32), jnp.asarray([0], jnp.int32)))
         for temp in (0.0, 0.7)],
        eng._sample_first))

    # ---- extend_row: partial prefix-cache hits (refused for families
    # whose rows hold a recurrent state: no prefix cache, no extension)
    if cfg.family in lm.RECURRENT_STATE_FAMILIES:
        return reports

    def extend_fn(qp, budget, tokens, row, start, r):
        wv, av = eng.controller.resolve(budget)
        return eng._extend_row(qp, tokens, row, start, r, wv, av)

    def extend_args(budget: float, start: int, r: int):
        return (q, jnp.asarray(budget, jnp.float32),
                jnp.zeros((1, PREFILL_LEN), jnp.int32), row,
                jnp.asarray(start, jnp.int32), jnp.asarray(r, jnp.int32))

    reports.append(audit_entrypoint(
        name, "extend_row",
        [(f"budget={b}/start={s}/r={r}",
          lambda b=b, s=s, r=r: extend_args(b, s, r))
         for b in BUDGETS[:2]
         for (s, r) in ((1, PREFILL_LEN - 1), (PREFILL_LEN - 1, 1))],
        extend_fn))

    if eng.spec_k is None:
        return reports

    # ---- draft_scan: SPEC_K_MAX low-bit self-draft -------------------
    def draft_fn(qp, tok, t, cache, temp, topk, ks):
        dwv, dav = eng._draft_bits()
        return eng._draft(qp, tok, t, cache, dwv, dav, temp, topk, ks)

    def draft_args(t0: int):
        return (q, jnp.zeros((B, 1), jnp.int32),
                jnp.full((B,), t0, jnp.int32), cache,
                jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
                keys(SPEC_K_MAX))

    reports.append(audit_entrypoint(
        name, "draft_scan",
        [(f"t={t0}", lambda t0=t0: draft_args(t0)) for t0 in (4, 9)],
        draft_fn))

    # ---- verify_chunk: one (SPEC_K_MAX + 1)-wide target-bit verify ---
    def verify_fn(qp, budgets, tok, dt, dp, t, cache, k_eff, temp, topk,
                  ku, ks_):
        wv, av = eng.controller.resolve(budgets)
        return eng._verify(qp, tok, dt, dp, t, cache, wv, av, k_eff,
                           temp, topk, ku, ks_)

    def verify_args(mix, k: int):
        import numpy as np
        return (q, slot_f32(mix), jnp.zeros((B, 1), jnp.int32),
                jnp.zeros((B, SPEC_K_MAX), jnp.int32),
                jax.ShapeDtypeStruct((B, SPEC_K_MAX, V), jnp.float32),
                jnp.zeros((B,), jnp.int32), cache,
                jnp.asarray(np.minimum(k, np.arange(1, B + 1)), jnp.int32),
                jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
                keys(1)[0], keys(2)[1])

    reports.append(audit_entrypoint(
        name, "verify_chunk",
        [(f"mix={mix}/k={k}",
          lambda mix=mix, k=k: verify_args(mix, k))
         for mix in BUDGET_MIXES[:2] for k in (0, 1, SPEC_K_MAX)],
        verify_fn))
    return reports


def _audit_model(name: str, cfg) -> List[TraceReport]:
    """Model-level audit for the whole-batch families (ssm/moe/hybrid/
    encdec): prefill + decode_step through generate()'s construction."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    from repro.models import lm

    B, S = 2, PREFILL_LEN
    L = lm.n_bit_slots(cfg)
    ctrl = _default_controller(L)
    q = abstract_qparams(cfg)
    cache = abstract_cache(cfg, B, MAX_LEN)
    fams = tuple(sorted({4, 8}))
    reports: List[TraceReport] = []

    def prefill_fn(qp, budget, tokens, cache, *extra):
        wv, av = ctrl.resolve(budget)
        batch = {"tokens": tokens}
        if cfg.family == "encdec":
            batch["frames"] = extra[0]
        with kops.bit_families(fams):
            return lm.prefill(qp, batch, cfg, wv, av, cache)

    def prefill_args(budget: float):
        extra = ()
        if cfg.family == "encdec":
            F = max(MAX_LEN // cfg.frames_ratio, 1)
            extra = (jax.ShapeDtypeStruct((B, F, cfg.d_model),
                                          jnp.float32),)
        return (q, jnp.asarray(budget, jnp.float32),
                jnp.zeros((B, S), jnp.int32), cache) + extra

    reports.append(audit_entrypoint(
        name, "prefill",
        [(f"budget={b}", lambda b=b: prefill_args(b)) for b in BUDGETS],
        prefill_fn))

    def decode_fn(qp, budget, tok, t, cache):
        wv, av = ctrl.resolve(budget)
        with kops.bit_families(fams):
            return lm.decode_step(qp, tok, t, cache, cfg, wv, av)

    def decode_args(budget: float, t0: int):
        return (q, jnp.asarray(budget, jnp.float32),
                jnp.zeros((B, 1), jnp.int32),
                jnp.full((B,), t0, jnp.int32), cache)

    reports.append(audit_entrypoint(
        name, "decode_step",
        [(f"budget={b}/t={t0}", lambda b=b, t0=t0: decode_args(b, t0))
         for b in BUDGETS[:3] for t0 in (S,)],
        decode_fn))
    return reports


def _audit_cnn() -> List[TraceReport]:
    """CNN conv-GEMM path: one abstract signature across every HAWQ-v3
    ResNet18 configuration (the paper's headline config-switching claim,
    statically)."""
    import jax
    import jax.numpy as jnp
    from repro.apsim.workloads import HAWQV3_RESNET18, per_layer_bits
    from repro.kernels import ops as kops
    from repro.models import cnn

    image = 16
    box: Dict[str, object] = {}

    def build(k):
        params, layers = cnn.init_cnn("resnet18", k, image=image)
        box["layers"] = layers
        return params

    params = jax.eval_shape(build, jax.random.PRNGKey(0))
    layers = box["layers"]
    qp = jax.eval_shape(
        lambda p: cnn.quantize_cnn_params(p, layers), params)

    def fwd(qparams, x, wv, av):
        with kops.bit_families((4, 8)):
            return cnn.cnn_forward(qparams, x, layers, wv, av)

    def args(vec):
        bits = jnp.asarray(per_layer_bits(layers, vec), jnp.int32)
        return (qp, jax.ShapeDtypeStruct((2, image, image, 3),
                                         jnp.float32), bits, bits)

    return [audit_entrypoint(
        "resnet18_hawq", "cnn_forward",
        [(cfg_name, lambda vec=vec: args(vec))
         for cfg_name, vec in HAWQV3_RESNET18.items()],
        fwd)]


def audit_config(name: str) -> List[TraceReport]:
    from repro import configs
    from repro.models import lm

    cfg = configs.get_smoke(name)
    if cfg.family in lm.RAGGED_PREFILL_FAMILIES:
        return _audit_engine(name, cfg)
    return _audit_model(name, cfg)


def run_retrace(arch_ids: Optional[Sequence[str]] = None,
                include_cnn: bool = True
                ) -> Tuple[List[Finding], List[TraceReport]]:
    """Audit every config (default: all ten) + the CNN path.  Returns
    (findings, reports); an empty findings list IS the static
    zero-retrace proof."""
    from repro import configs

    reports: List[TraceReport] = []
    for name in (arch_ids if arch_ids is not None else configs.ARCH_IDS):
        reports.extend(audit_config(name))
    if include_cnn:
        reports.extend(_audit_cnn())
    findings = [f for r in reports for f in r.findings()]
    return findings, reports
