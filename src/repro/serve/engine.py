"""Continuous-batching LM serving: the prefill/decode workload adapter.

One compiled prefill + one compiled decode program serve every precision
configuration AND every mix of configurations across a batch: each
request carries its own latency budget, resolved by a
:class:`repro.core.policy.BudgetController` (or closed-loop
:class:`~repro.core.policy.FluidController`) into a per-layer bit
vector, and the batch's ``(B, n_layers)`` bit *matrix* is an ordinary
traced input — the TPU realization of the paper's §V.B dynamic
mixed-precision claim, at request granularity (cf. LRMP,
arXiv:2312.03146).

The queue, EDP-aware admission scheduler, slot lifecycle, closed
control loop, pricing, and stats all live in the workload-agnostic
:class:`repro.serve.runtime.ServeRuntime` (DESIGN.md §8); this module
owns only what is LM-shaped — ragged prefill, the scan-fused decode
block, per-row sampling, and the KV cache pool.

  * prefill runs per admitted request on a fixed ``(1, prefill_len)``
    shape (right-padded, EMPTY_POS-masked), its cache row installed into
    a persistent :class:`repro.models.lm.CachePool` by a traced-index
    write — slot churn never retraces.
  * decode is scan-fused: ``decode_block`` tokens per dispatch via
    ``lax.scan`` over (decode_step -> sample), with per-row positions,
    per-row bits, and per-row sampling (greedy / temperature / top-k).
  * ``stats`` counts traces; tests assert both programs compile exactly
    once across budget churn, slot reuse, and closed-loop switches.

The legacy whole-batch API (``set_budget``/``generate``) is kept — it
accepts a per-request budget *vector* and runs the same scan-fused
decode.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import dist
from repro.core.policy import (BudgetController, FluidController,
                               PrecisionPolicy)
from repro.dist import sharding as shd
from repro.models import lm
from repro.models.transformer import EMPTY_POS
from repro.serve.accounting import RequestStats, RuntimeStats  # noqa: F401
from repro.serve.prefix_cache import PrefixCache
from repro.serve.runtime import (ServeRuntime, SlotTable,
                                 UNCONSTRAINED_BUDGET)

TOPK_MAX = 64          # static top-k sort width; per-row k <= TOPK_MAX
SPEC_K_MAX = 8         # static draft depth ceiling: every speculative
                       # round drafts SPEC_K_MAX tokens and verifies one
                       # (SPEC_K_MAX + 1)-wide chunk, so ONE compiled
                       # draft program and ONE verify program cover every
                       # (slot, k, accept-length) combination


@dataclasses.dataclass
class Request:
    """A queued generation request with its own budget + sampling params."""
    rid: int
    prompt: np.ndarray                  # (S,) int32
    max_new_tokens: int
    budget_s: Optional[float]
    temperature: float = 0.0
    top_k: int = 0
    prefix: Optional[np.ndarray] = None  # vlm: (n_prefix_tokens, d) stub
    rep_key: Optional[int] = None       # traffic repetition key (the
                                        # prefix-cache count signal)
    draft_k: Optional[int] = None       # speculative draft depth override
                                        # (None: engine/controller decides)


def _scaled_logits(logits: jnp.ndarray, temperature: jnp.ndarray,
                   top_k: jnp.ndarray) -> jnp.ndarray:
    """Per-row masked + temperature-scaled logits: logits (B, V);
    temperature/top_k (B,).  top_k > 0 masks all but the row's k best
    logits (static TOPK_MAX sort width, per-row threshold gather).  The
    single definition of the sampling distribution — sampling draws from
    softmax of this, and speculative rejection-accept tests drafts
    against the same densities."""
    V = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    K = min(TOPK_MAX, V)
    vals, _ = jax.lax.top_k(logits, K)                       # (B, K)
    kth = jnp.take_along_axis(vals, jnp.clip(top_k, 1, K)[:, None] - 1,
                              axis=1)                        # (B, 1)
    masked = jnp.where((top_k[:, None] > 0) & (logits < kth),
                       -jnp.inf, logits)
    return masked / jnp.maximum(temperature, 1e-6)[:, None]


def _sample_tokens(logits: jnp.ndarray, key, temperature: jnp.ndarray,
                   top_k: jnp.ndarray) -> jnp.ndarray:
    """Per-row sampling: logits (B, V); temperature/top_k (B,).
    temperature == 0 -> greedy."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = _scaled_logits(logits, temperature, top_k)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


class ServeEngine(ServeRuntime):
    """Continuous-batching, bit-fluid LM serving engine.

    Two APIs share the compiled programs:

      * whole-batch: ``set_budget(scalar | (B,) vector)`` +
        ``generate(batch, steps)`` — one synchronous batch.
      * continuous: ``submit(prompt, budget_s=..., ...) -> rid`` +
        ``run()`` (or ``step()`` for manual pumping) — requests stream
        through a persistent slot pool, each at its own precision.
    """

    def __init__(self, cfg, qparams, *, max_len: int = 256,
                 controller: Optional[BudgetController] = None,
                 policy: Optional[PrecisionPolicy] = None,
                 mesh=None, n_slots: int = 4, prefill_len: int = 32,
                 decode_block: int = 8, eos_id: Optional[int] = None,
                 seed: int = 0, prefix_cache: Optional[PrefixCache] = None,
                 spec_k: Optional[int] = None,
                 draft_budget_s: Optional[float] = None,
                 plan=None):
        self.cfg = cfg
        # ---- speculative decoding (DESIGN.md §11): spec_k=None disables
        # entirely; an int enables self-drafting with that default depth
        # (a FluidController overrides per admission via draft_depth()).
        # draft_budget_s picks the DRAFT bit configuration through the
        # same controller tables (None -> 0.0 -> the cheapest config).
        if spec_k is not None:
            if not 0 <= spec_k <= SPEC_K_MAX:
                raise ValueError(
                    f"spec_k={spec_k} not in [0, {SPEC_K_MAX}]")
            if cfg.sliding_window:
                raise ValueError(
                    "speculative decoding needs a non-wrapping KV ring; "
                    "sliding_window models must serve with spec_k=None")
            if cfg.family not in lm.SPEC_CHUNK_FAMILIES:
                raise ValueError(
                    f"speculative decoding needs the chunked verify path; "
                    f"family {cfg.family!r} is unsupported "
                    f"(supported: {lm.SPEC_CHUNK_FAMILIES})")
        # a recurrent state holds every token a row has seen: it cannot be
        # cut back to a cached prefix (DESIGN.md §10 needs that cut)
        self._recurrent = cfg.family in lm.RECURRENT_STATE_FAMILIES
        if self._recurrent and prefix_cache is not None:
            raise ValueError(
                f"the prefix cache installs a row cut back to a shared "
                f"prefix; family {cfg.family!r} keeps a recurrent state "
                f"per row, which cannot be cut — serve it with "
                f"prefix_cache=None")
        self.spec_k = spec_k
        self._draft_budget_f = (0.0 if draft_budget_s is None
                                else float(draft_budget_s))
        self._draft_bits_c = None
        self._draft_price = None
        self._draft_idx = -1            # config index the draft caches hold
        self._draft_price_idx = -1
        self._draft_wbits_f = 0.0       # mean weight bits of that config
        mesh = mesh if mesh is not None else dist.active_mesh()
        self.qparams = qparams
        self.max_len = max_len
        self.n_slots = n_slots
        self.prefill_len = prefill_len
        self.decode_block = decode_block
        self.eos_id = eos_id
        n = lm.n_bit_slots(cfg)
        if controller is None:
            pol = policy or _default_policy()
            controller = BudgetController({pol.name: pol}, {pol.name: 0.0}, n)
        if (controller.budget_axis != "latency"
                and not isinstance(controller, FluidController)):
            # a FluidController may run its SLO loop on the energy/EDP
            # axis (AP latency is nearly flat across precisions — Table
            # VII — so only energy-family budgets can discriminate);
            # request budgets then live on that axis too.  An OPEN-loop
            # controller on a non-latency axis is a wiring bug: LM
            # budgets are seconds, so they would always- or never-fit.
            raise ValueError(
                f"ServeEngine budgets are LATENCY budgets (seconds) but the "
                f"controller's prediction table lives on the "
                f"{controller.budget_axis!r} axis — its budgets would "
                f"always- or never-fit; build the controller with "
                f"latency predictions (cnn_budget_controller's "
                f"energy/EDP axes are for CNNServeEngine, or use a "
                f"FluidController for an energy/EDP SLO loop)")
        super().__init__(controller, n, gemms=lm.layer_gemm_dims(cfg),
                         head=lm.head_gemm_dims(cfg), mesh=mesh, plan=plan)
        if self.mesh is not None:       # place serve weights once, sharded
            # (after super().__init__ so an "auto" plan is resolved —
            # fully-replicated plan entries override the FSDP/tp rules)
            self.qparams = jax.device_put(
                self.qparams, shd.param_shardings(self.qparams, self.mesh,
                                                  plan=self.plan))
        # scale-out execution gate (DESIGN.md §13): a fully-replicated
        # plan makes every device hold every weight, so the decode block
        # can run under shard_map with request ROWS split across the dp
        # axis — manual per-device compute is exact (rows are
        # independent; greedy sampling is bit-identical).  Partial plans
        # and plan-less meshes keep the GSPMD path.
        self._dp_exec = None
        if (self.plan is not None and self.mesh is not None
                and self.plan.fully_replicated):
            dpx = dist.mesh_axes_for(self.mesh, "dp")
            dp = dist.dp_size(self.mesh)
            if dpx and dp > 1 and n_slots % dp == 0:
                self._dp_exec = dpx[0] if len(dpx) == 1 else tuple(dpx)
        self.budget_s = jnp.asarray(1e9, jnp.float32)
        self.row_bits = cfg.family in lm.PER_ROW_BIT_FAMILIES
        self._key = jax.random.PRNGKey(seed)
        # cross-request prefix/KV-cache tier (DESIGN.md §10): only
        # prompts that fit the cache ring entirely are cacheable (a
        # wrapped prefix would install an incomplete row), and vlm
        # requests bypass (their prefix embeddings aren't content-keyed)
        self.prefix_cache = prefix_cache
        self._cache_sc = (min(max_len, cfg.sliding_window)
                          if cfg.sliding_window else max_len)

        # ---- continuous-batching state (pool built lazily on first submit)
        self.pool: Optional[lm.CachePool] = None
        self.slots = SlotTable(
            n_slots,
            tok=(np.int64, 0), t=(np.int64, 0),
            budget=(np.float64, 0.0),           # freed rows: cheapest bits
            temp=(np.float64, 0.0), topk=(np.int64, 0),
            remaining=(np.int64, 0),
            k=(np.int64, 0))                    # speculative draft depth
        self._just_finished: List[int] = []

        # ---- compiled programs (each traces exactly once per shape)
        @self.stats.traced("prefill")
        def _prefill_batch(q, batch, cache, wv, av):
            return lm.prefill(q, batch, cfg, wv, av, cache)

        @self.stats.traced("prefill")
        def _prefill_row(q, tokens, length, wv, av, *prefix):
            cache = lm.empty_cache(cfg, 1, max_len)
            batch = {"tokens": tokens}
            if prefix:                  # vlm: (1, n_prefix_tokens, d)
                batch["prefix"] = prefix[0]
            return lm.prefill(q, batch, cfg, wv, av, cache, lengths=length)

        @self.stats.traced("decode")
        def _decode_scan(q, tok, t, cache, wv, av, temp, topk, keys):
            def step(carry, key):
                tok, t, cache = carry
                logits, cache = lm.decode_step(q, tok, t, cache, cfg, wv, av)
                nxt = _sample_tokens(logits[:, -1], key, temp, topk)
                return (nxt[:, None], t + 1, cache), nxt

            (tok, t, cache), toks = jax.lax.scan(step, (tok, t, cache), keys)
            return tok, t, cache, jnp.moveaxis(toks, 0, 1)   # (B, steps)

        @self.stats.traced("draft")
        def _draft_scan(q, tok, t, cache, wv, av, temp, topk, keys):
            # speculative self-draft: SPEC_K_MAX scan-fused decode steps
            # at the engine's LOW draft bits (one program for every k —
            # rows with shallower depth simply ignore the tail).  Also
            # returns each draft's sampling density q_i: the rejection
            # verify tests p_i/q_i against the same distributions the
            # tokens were drawn from.

            def step(carry, key):
                tok, t, cache = carry
                logits, cache = lm.decode_step(q, tok, t, cache, cfg, wv, av)
                flat = logits[:, -1].astype(jnp.float32)
                nxt = _sample_tokens(flat, key, temp, topk)
                probs = jax.nn.softmax(
                    _scaled_logits(flat, temp, topk), axis=-1)
                return (nxt[:, None], t + 1, cache), (nxt, probs)

            (_, _, cache), (toks, probs) = jax.lax.scan(
                step, (tok, t, cache), keys)
            return (jnp.moveaxis(toks, 0, 1),        # (B, SPEC_K_MAX)
                    jnp.moveaxis(probs, 0, 1),       # (B, SPEC_K_MAX, V)
                    cache)

        @self.stats.traced("verify")
        def _spec_verify(q, tok, draft_toks, draft_probs, t, cache,
                         wv, av, k_eff, temp, topk, key_u, key_s):
            # batched high-bit verify: ONE (SPEC_K_MAX + 1)-wide chunk
            # scores the current token + every draft at each row's own
            # TARGET bits, overwriting the draft-precision cache entries
            # in place.  Greedy rows accept the longest exact-argmax
            # prefix; sampled rows run rejection resampling against the
            # draft densities (accept u < p/q, resample the first
            # rejection from normalize(max(p - q, 0)), bonus draw from p
            # on full accept) — both paths emit a + 1 tokens.  k_eff is
            # the per-row accept clamp (min(spec_k, remaining - 1)), so
            # one compiled program covers every (slot, k, accept-length)
            # combination.
            B = tok.shape[0]
            U = SPEC_K_MAX + 1
            toks = jnp.concatenate([tok, draft_toks], axis=1)     # (B, U)
            logits, cache = lm.decode_chunk(q, toks, t, cache, cfg, wv, av)
            logits = logits.astype(jnp.float32)
            ver = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (B, U)
            flat = logits.reshape(B * U, -1)
            p = jax.nn.softmax(
                _scaled_logits(flat, jnp.repeat(temp, U),
                               jnp.repeat(topk, U)), axis=-1
            ).reshape(B, U, -1)                   # per-position target dists
            p_g = jnp.take_along_axis(p[:, :SPEC_K_MAX],
                                      draft_toks[..., None],
                                      axis=-1)[..., 0]            # (B, K)
            q_g = jnp.take_along_axis(draft_probs, draft_toks[..., None],
                                      axis=-1)[..., 0]
            u = jax.random.uniform(key_u, draft_toks.shape)
            ok = jnp.where(temp[:, None] > 0,
                           u * jnp.maximum(q_g, 1e-20) < p_g,     # u < p/q
                           draft_toks == ver[:, :SPEC_K_MAX])
            ok &= jnp.arange(SPEC_K_MAX)[None] < k_eff[:, None]
            a = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                        axis=1)                  # accepted prefix length
            # the (a+1)-th emitted token: greedy rows take the verify
            # argmax at position a; sampled rows draw from the residual
            # (or from p itself at a == k_eff, the full-accept bonus)
            p_a = jnp.take_along_axis(p, a[:, None, None], axis=1)[:, 0]
            q_pad = jnp.concatenate(
                [draft_probs, jnp.zeros_like(draft_probs[:, :1])], axis=1)
            q_a = jnp.take_along_axis(q_pad, a[:, None, None], axis=1)[:, 0]
            resid = jnp.where((a < k_eff)[:, None],
                              jnp.maximum(p_a - q_a, 0.0), p_a)
            tot = jnp.sum(resid, axis=-1, keepdims=True)
            rdist = jnp.where(tot > 0, resid / jnp.maximum(tot, 1e-30), p_a)
            extra = jnp.where(
                temp > 0,
                jax.random.categorical(
                    key_s, jnp.log(rdist + 1e-30),
                    axis=-1).astype(jnp.int32),
                jnp.take_along_axis(ver, a[:, None], axis=1)[:, 0])
            emitted = jnp.where(
                jnp.arange(U)[None] < a[:, None],
                jnp.concatenate([draft_toks, draft_toks[:, -1:]], axis=1),
                extra[:, None])                                   # (B, U)
            # extra is the round's LAST delivered token = next round's
            # input; keep = t + a is the rollback watermark (entries past
            # it were computed from rejected drafts)
            return extra, t + a + 1, emitted, a + 1, t + a, cache

        def _sample_first(logits, key, temp, topk):
            return _sample_tokens(logits[:, -1], key, temp, topk)

        @self.stats.traced("extend")
        def _extend_row(q, tokens, row, start, r, wv, av):
            # partial prefix-cache hit: the entry's row holds a longer
            # (or equal) prompt — mask it down to its first ``start``
            # tokens, then push the remaining ``r`` prompt tokens
            # through the decode path at positions start..start+r-1.
            # Fixed scan length (prefill_len) with a clamped step index
            # keeps the shape static: start/r are traced scalars, so
            # every partial hit shares ONE compiled program; the
            # clamped tail steps recompute the final token with
            # identical inputs (idempotent cache writes).  The entry's
            # pytree is never donated — the cache keeps its rows.

            def mask(path, p):
                if path and path[-1] == "kpos":
                    return jnp.where(p >= start, EMPTY_POS, p)
                return p

            row = jax.tree_util.tree_map_with_path(
                lambda path, p: mask(tuple(
                    str(getattr(k, "key", k)) for k in path), p), row)

            def step(cache, s):
                s_eff = jnp.minimum(s, r - 1)
                tok = jax.lax.dynamic_slice(tokens, (0, start + s_eff),
                                            (1, 1))
                logits, cache = lm.decode_step(q, tok, start + s_eff,
                                               cache, cfg, wv, av)
                return cache, logits

            row, ys = jax.lax.scan(
                step, row, jnp.arange(prefill_len, dtype=jnp.int32))
            return ys[-1], row          # final-token logits (1, 1, V)

        self._prefill = jax.jit(_prefill_batch, donate_argnums=(2,))
        self._prefill_row = jax.jit(_prefill_row)
        self._decode_scan = jax.jit(_decode_scan, donate_argnums=(3,))
        self._decode_scan_sh = None
        if self._dp_exec is not None:
            from jax.sharding import PartitionSpec as P

            dpx = self._dp_exec

            def _prefill_row_manual(*args):
                with dist.manual_mode():
                    return _prefill_row(*args)

            # GSPMD cannot partition a Pallas (Mosaic) kernel, and one row
            # does not split across dp: every device prefills the row
            # whole from replicated weights (inputs and outputs replicated)
            self._prefill_row = jax.jit(jax.shard_map(
                _prefill_row_manual, mesh=self.mesh, in_specs=P(),
                out_specs=P(), check_vma=False))

            def _decode_scan_manual(*args):
                # trace-time flag: constrain() inside the body must
                # no-op (mesh axes are consumed by the shard_map)
                with dist.manual_mode():
                    return _decode_scan(*args)

            # (q, tok, t, cache, wv, av, temp, topk, keys): weights
            # replicated (the plan's point), every per-request operand
            # split on its batch dim — cache leaves all carry batch at
            # dim 1, so one prefix spec covers the whole pytree
            self._decode_scan_sh = jax.jit(
                jax.shard_map(
                    _decode_scan_manual, mesh=self.mesh,
                    in_specs=(P(), P(dpx, None), P(dpx), P(None, dpx),
                              P(dpx, None), P(dpx, None), P(dpx),
                              P(dpx), P()),
                    out_specs=(P(dpx, None), P(dpx), P(None, dpx),
                               P(dpx, None)),
                    check_vma=False),
                donate_argnums=(3,))
        self._draft = jax.jit(_draft_scan, donate_argnums=(3,))
        self._verify = jax.jit(_spec_verify, donate_argnums=(5,))
        self._sample_first = jax.jit(_sample_first)
        self._extend_row = jax.jit(_extend_row)

    # ------------------------------------------------------------------
    # Shared plumbing
    # ------------------------------------------------------------------

    def set_budget(self, seconds) -> None:
        """Runtime knob: a scalar batch budget, or a (B,) per-request
        budget vector — either way pure data, no recompilation."""
        self.budget_s = jnp.asarray(seconds, jnp.float32)

    def _bits(self):
        wv, av = self.controller.resolve(self.budget_s)
        if wv.ndim == 2 and not self.row_bits:
            raise NotImplementedError(
                f"per-request budgets need per-row bit support; family "
                f"{self.cfg.family!r} serves whole-batch budgets only "
                f"(supported: {lm.PER_ROW_BIT_FAMILIES})")
        return wv, av

    def price_budget(self, budget_s: float):
        """Per-token AP cost of the configuration a scalar budget selects."""
        return self.price_bits(
            *self.controller.resolve(jnp.asarray(budget_s, jnp.float32)))

    def _draft_index(self) -> int:
        """Stacked-config index the drafts run at: the draft budget's
        base config, offset by a FluidController's autotuner shift
        (``observe_accept`` — accept-rate EMA moves draft bits up or
        down), clamped into the config range."""
        base = self._host_index(self._draft_budget_f)
        shift = int(getattr(self.controller, "draft_shift", 0) or 0)
        n = self.host_tables()[0].shape[0]
        return min(max(base + shift, 0), n - 1)

    def _draft_bits(self):
        """Device-side draft bit matrix (n_slots, L): the current draft
        config broadcast across rows.  Cached per config index — the
        shapes never change, so an autotuner shift swaps pure data
        without retracing."""
        idx = self._draft_index()
        if self._draft_bits_c is None or idx != self._draft_idx:
            wtab, atab = self.controller.stacked_tables()
            wv, av = wtab[idx], atab[idx]
            wv = jnp.broadcast_to(wv, (self.n_slots,) + wv.shape)
            av = jnp.broadcast_to(av, (self.n_slots,) + av.shape)
            if self.mesh is not None:
                wv = shd.shard_bits(wv, self.mesh)
                av = shd.shard_bits(av, self.mesh)
            self._draft_bits_c = (wv, av)
            self._draft_idx = idx
            self._draft_wbits_f = float(np.mean(self.host_tables()[0][idx]))
            self._draft_price = None
        return self._draft_bits_c

    def _draft_pricing(self):
        """Per-token AP cost of one draft step at the current draft bits
        (cached per config index; plan-amortized when a placement plan
        is installed)."""
        idx = self._draft_index()
        if self._draft_price is None or idx != self._draft_price_idx:
            wtab, atab = self.host_tables()
            self._draft_price = self.price_bits(wtab[idx], atab[idx])
            self._draft_price_idx = idx
            self._draft_wbits_f = float(np.mean(wtab[idx]))
        return self._draft_price

    def _resolve_draft_k(self, req: Request) -> int:
        """Draft depth for one admission: the request's explicit
        ``draft_k``, else the FluidController's headroom-scaled depth,
        else the engine default (spec_k=None disables)."""
        if req.draft_k is not None:
            return int(req.draft_k)
        if self.spec_k is None:
            return 0
        if isinstance(self.controller, FluidController):
            return min(self.controller.draft_depth(), SPEC_K_MAX)
        return self.spec_k

    def _split_key(self, num: int):
        keys = jax.random.split(self._key, num + 1)
        self._key = keys[0]
        return keys[1:]

    # ------------------------------------------------------------------
    # Whole-batch API (legacy-compatible, now scan-fused)
    # ------------------------------------------------------------------

    def generate(self, batch: Dict[str, jnp.ndarray], steps: int, *,
                 temperature=None, top_k=None) -> jnp.ndarray:
        """Generate ``steps`` tokens for one synchronous batch; returns
        (B, steps) ids.  Greedy unless per-row temperature/top_k given."""
        if isinstance(self.controller, FluidController):
            # the whole-batch path has no admissions to charge — it would
            # silently run the fluid controller open-loop
            raise ValueError(
                "the whole-batch generate() API is open-loop; a "
                "FluidController's SLO window is only charged by the "
                "continuous scheduler — use submit()/run()")
        with self.compute_ctx():
            return self._generate(batch, steps, temperature, top_k)

    def _generate(self, batch, steps, temperature, top_k):
        B, S = batch["tokens"].shape
        prefix = self.cfg.n_prefix_tokens if self.cfg.family == "vlm" else 0
        temp = jnp.zeros((B,), jnp.float32) if temperature is None else \
            jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
        if top_k is not None and int(np.max(np.asarray(top_k))) > TOPK_MAX:
            raise ValueError(f"top_k exceeds TOPK_MAX={TOPK_MAX}")
        topk = jnp.zeros((B,), jnp.int32) if top_k is None else \
            jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
        wv, av = self._bits()
        if self.mesh is not None:
            wv, av = shd.shard_bits(wv, self.mesh), shd.shard_bits(av,
                                                                   self.mesh)
        batch = shd.shard_batch(batch, self.mesh)
        cache = lm.empty_cache(self.cfg, B, self.max_len)
        if self.mesh is not None:
            cache = jax.device_put(cache, shd.cache_shardings(cache,
                                                              self.mesh))
        logits, cache = self._prefill(self.qparams, batch, cache, wv, av)
        keys = self._split_key(steps)
        tok = self._sample_first(logits, keys[0], temp, topk)[:, None]
        t = jnp.full((B,), S + prefix, jnp.int32)
        _, _, cache, toks = self._decode_scan(
            self.qparams, tok, t, cache, wv, av, temp, topk, keys[1:steps])
        out = jnp.concatenate([tok, toks], axis=1)
        self.stats.tokens += B * steps
        return out

    # ------------------------------------------------------------------
    # Continuous-batching API
    # ------------------------------------------------------------------

    def submit(self, prompt, *, max_new_tokens: int = 16,
               budget_s: Optional[float] = None, temperature: float = 0.0,
               top_k: int = 0, prefix=None,
               rep_key: Optional[int] = None,
               draft_k: Optional[int] = None) -> int:
        """Enqueue a request; returns its id.  ``budget_s`` caps this
        request's precision configuration (None = loosest/most accurate;
        under a FluidController the closed loop may tighten it further).
        vlm models require ``prefix`` (n_prefix_tokens, d_model).
        ``rep_key`` threads a traffic repetition key to the prefix-cache
        tier (hits are content-keyed either way; the key feeds the
        repetition-aware eviction value).  ``draft_k`` overrides the
        speculative draft depth for this request (0 = vanilla decode;
        None = the engine/controller decides)."""
        if self.cfg.family not in lm.RAGGED_PREFILL_FAMILIES:
            raise NotImplementedError(
                f"the continuous-batching API needs ragged prefill; family "
                f"{self.cfg.family!r} serves via generate() only "
                f"(supported: {lm.RAGGED_PREFILL_FAMILIES})")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if not 1 <= prompt.shape[0] <= self.prefill_len:
            raise ValueError(f"prompt length {prompt.shape[0]} not in "
                             f"[1, {self.prefill_len}]")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens={max_new_tokens} must be >= 1")
        prefix_len = (self.cfg.n_prefix_tokens
                      if self.cfg.family == "vlm" else 0)
        if (prefix_len + self.prefill_len + max_new_tokens > self.max_len
                and not self.cfg.sliding_window):
            raise ValueError("prefix + prefill_len + max_new_tokens "
                             "exceeds max_len (KV ring would wrap)")
        if top_k > TOPK_MAX:
            raise ValueError(f"top_k={top_k} exceeds TOPK_MAX={TOPK_MAX}")
        if draft_k is not None and not 0 <= draft_k <= SPEC_K_MAX:
            raise ValueError(f"draft_k={draft_k} not in [0, {SPEC_K_MAX}]")
        # speculative rounds write up to SPEC_K_MAX positions past the
        # accepted point before rollback — the KV ring must never wrap
        # under them (wrapped slots would expose stale-lap entries to
        # the chunked verify).  Enforced whenever this request COULD
        # draft: an explicit draft_k > 0, or a spec-enabled engine whose
        # controller may pick k > 0 at admission time.
        spec_possible = (draft_k or 0) > 0 or (
            draft_k is None and self.spec_k is not None
            and (self.spec_k > 0
                 or isinstance(self.controller, FluidController)))
        if spec_possible:
            if self.cfg.sliding_window:
                raise ValueError(
                    "speculative decoding needs a non-wrapping KV ring; "
                    "sliding_window requests must submit draft_k=0")
            if self.cfg.family not in lm.SPEC_CHUNK_FAMILIES:
                raise ValueError(
                    f"speculative decoding unsupported for family "
                    f"{self.cfg.family!r} "
                    f"(supported: {lm.SPEC_CHUNK_FAMILIES})")
            if (prefix_len + self.prefill_len + max_new_tokens
                    + SPEC_K_MAX > self.max_len):
                raise ValueError(
                    "prefix + prefill_len + max_new_tokens + SPEC_K_MAX "
                    "exceeds max_len (a speculative round could wrap the "
                    "KV ring); submit draft_k=0 or shrink the request")
        if self.cfg.family == "vlm":
            if prefix is None:
                raise ValueError("vlm requests need a prefix "
                                 "(n_prefix_tokens, d_model)")
            prefix = np.asarray(prefix, np.float32)
            if prefix.shape != (self.cfg.n_prefix_tokens, self.cfg.d_model):
                raise ValueError(f"prefix shape {prefix.shape} != "
                                 f"({self.cfg.n_prefix_tokens}, "
                                 f"{self.cfg.d_model})")
        rid = self.next_rid()
        with self.stats.span("request.submit", rid=rid):
            req = Request(rid, prompt, max_new_tokens,
                          None if budget_s is None else float(budget_s),
                          float(temperature), int(top_k), prefix=prefix,
                          rep_key=rep_key, draft_k=draft_k)
            record = RequestStats(
                rid=rid,
                budget_s=(float(budget_s) if budget_s is not None
                          else UNCONSTRAINED_BUDGET),
                prompt_len=int(prompt.shape[0]), submitted_s=time.time())
            est_scale = 1.0
            if self._cacheable(req):
                # admission planner sees the predicted hit: the modeled
                # EDP is discounted by the predicted cached fraction, so
                # likely hits admit earlier — they really are cheaper to
                # serve
                total = prompt.shape[0] + max_new_tokens
                est_scale = max(total - self.prefix_cache.peek(prompt),
                                1) / total
            return self.new_record(record, req, budget_s,
                                   est_scale=est_scale)

    def _ensure_pool(self) -> lm.CachePool:
        if self.pool is None:
            shardings = None
            if self.mesh is not None:
                proto = lm.empty_cache(self.cfg, self.n_slots, self.max_len)
                shardings = shd.cache_shardings(proto, self.mesh)
            self.pool = lm.CachePool(self.cfg, self.n_slots, self.max_len,
                                     shardings=shardings)
        return self.pool

    def _cacheable(self, req: Request) -> bool:
        return (self.prefix_cache is not None and req.prefix is None
                and req.prompt.shape[0] <= self._cache_sc)

    def _admit(self) -> List[int]:
        """Move queued requests into free pool slots, in the runtime's
        EDP-aware, starvation-free admission order.  With a prefix
        cache, each admission consults the tier before prefilling: a
        full hit installs the cached row and reuses its stored logits
        (prefill skipped entirely), a partial hit installs the shared
        prefix and extends the remainder through the decode path, and a
        miss prefills fresh and stores/refreshes the entry.  Only the
        miss fraction is charged against a FluidController's window."""
        pool = self._ensure_pool()
        admitted = []
        while self.queued and pool.free_slots:
            req: Request = self.next_admission()
            with self.stats.span("admit.request", rid=req.rid):
                self._admit_request(req, pool)
            admitted.append(req.rid)
        return admitted

    def _admit_request(self, req: Request, pool: lm.CachePool) -> None:
        """One admission, in three spans: ``admit.plan`` (budget, bits,
        prefix lookup, draft depth, AP pricing), ``prefill.dispatch``
        (the row's prefill, extension or install, and the first token's
        sampling) and ``admit.sync`` (that token's read-back)."""
        S = req.prompt.shape[0]
        record = self.requests[req.rid]
        with self.stats.span("admit.plan"):
            slot = pool.alloc()
            planned = S + req.max_new_tokens
            hit = wv_np = av_np = None
            # resolve the effective budget HOST-side first: the prefix
            # cache's precision gate and the speculative plan's pricing
            # both need the bits before any charging
            eff = self.admission_budget(req.budget_s)
            if self._cacheable(req):
                wv_np, av_np = self.host_bits(eff)
                hit = self.prefix_cache.lookup(
                    req.prompt, wv_np, av_np, rep_key=req.rep_key)
            cached = hit.keep if hit is not None else 0
            # speculative plan: charge draft + verify pricing for the
            # planned rounds at admission (full-accept plan; the honest
            # per-round actuals reconcile at finish)
            k_req = self._resolve_draft_k(req)
            spec = None
            if k_req > 0 and req.max_new_tokens > 1:
                swv, sav = self.host_bits(eff)
                spec = (k_req, self._draft_pricing(),
                        self.price_verify_bits(swv, sav, k_req + 1),
                        -(-(req.max_new_tokens - 1) // (k_req + 1)),
                        req.max_new_tokens - 1)
            else:
                k_req = 0
            wv, av = self.admit_record(record, req.budget_s, planned,
                                       eff=eff,
                                       charge_units=planned - cached,
                                       spec=spec)
            if hit is not None:
                record.cached_units = cached
                record.cache_hit = "full" if hit.full else "partial"
                record.cached_cost = self.price_bits(hit.entry.wbits,
                                                     hit.entry.abits)
                record.cached_mean_wbits = float(
                    np.mean(hit.entry.wbits))
                self.prefix_cache.ledger.prefill_edp_saved_js += \
                    record.prefill_edp_saved_js
        with self.stats.span("prefill.dispatch"):
            tokens = np.zeros((1, self.prefill_len), np.int32)
            tokens[0, :S] = req.prompt
            if hit is not None and hit.full:
                # full hit: the cached row IS the prefill output at the
                # entry's bits — install it and reuse its stored logits
                pool.install_prefix(hit.entry.row_cache, slot, S)
                logits = hit.entry.logits
                prefix_len = 0
            elif hit is not None:
                # partial hit: install the shared prefix, extend the
                # rest through the compiled decode-extension program
                # (a scan of prefill_len positions)
                logits, row_cache = self._extend_row(
                    self.qparams, jnp.asarray(tokens),
                    hit.entry.row_cache, jnp.asarray(cached, jnp.int32),
                    jnp.asarray(S - cached, jnp.int32), wv, av)
                pool.write_row(row_cache, slot, S)
                prefix_len = 0
                self.stats.prefill_by_rid[req.rid] = (S - cached,
                                                      self.prefill_len)
                # refresh only when precision-pure: the extended row
                # mixes entry bits (prefix) with resolved bits (tail)
                # unless they match
                if (np.array_equal(hit.entry.wbits, wv_np)
                        and np.array_equal(hit.entry.abits, av_np)):
                    self.prefix_cache.store(
                        req.prompt, row_cache, logits, wv_np, av_np,
                        record.ap_cost, rep_key=req.rep_key)
            else:
                extra = (() if req.prefix is None
                         else (jnp.asarray(req.prefix[None]),))
                logits, row_cache = self._prefill_row(
                    self.qparams, jnp.asarray(tokens),
                    jnp.asarray([S], jnp.int32), wv, av, *extra)
                prefix_len = (self.cfg.n_prefix_tokens
                              if self.cfg.family == "vlm" else 0)
                with (self.stats.span("prefill.state_install")
                      if self._recurrent else contextlib.nullcontext()):
                    pool.write_row(row_cache, slot, S + prefix_len)
                self.stats.prefill_by_rid[req.rid] = (
                    S + prefix_len, self.prefill_len + prefix_len)
                if wv_np is not None:   # cacheable miss: store/refresh
                    self.prefix_cache.store(
                        req.prompt, row_cache, logits, wv_np, av_np,
                        record.ap_cost, rep_key=req.rep_key)
            key = self._split_key(1)[0]
            first = self._sample_first(
                logits, key, jnp.asarray([req.temperature], jnp.float32),
                jnp.asarray([req.top_k], jnp.int32))
        # the unavoidable per-admission sync (eos/stream bookkeeping
        # needs the sampled token on the host) — exactly one transfer
        with self.stats.span("admit.sync"):
            first0 = int(jax.device_get(first)[0])
        record.slot = slot
        record.tokens.append(first0)
        self.stats.tokens += 1
        self.slots.occupy(slot, req.rid, tok=first0,
                          t=S + prefix_len, budget=record.budget_s,
                          temp=req.temperature, topk=req.top_k,
                          remaining=req.max_new_tokens - 1, k=k_req)
        if self.slots["remaining"][slot] <= 0 or (
                self.eos_id is not None
                and first0 == self.eos_id):
            self._finish(slot)

    def _finish(self, slot: int) -> None:
        rid = int(self.slots.rid[slot])
        self.finish_record(rid)
        self.slots.release(slot)
        self.pool.free(slot)
        self._just_finished.append(rid)

    def _has_active(self) -> bool:
        return bool(self.slots.active.any())

    def _active_count(self) -> int:
        return int(self.slots.active.sum())

    def _can_admit(self) -> bool:
        return self.n_slots >= 1

    def step(self) -> List[int]:
        """One scheduler tick: admit into free slots, decode one block,
        harvest tokens, retire finished requests.  Returns the rids that
        completed during this tick."""
        with self.compute_ctx():
            return self._step()

    def _step(self) -> List[int]:
        with self.stats.span("admit"):
            self.age_queue()
            self._admit()
        slots = self.slots
        active = slots.active
        if active.any():
            # a round can accept at most remaining - 1 drafts (the +1
            # verified token must not overshoot max_new_tokens), so a
            # batch whose every row is clamped to 0 takes the vanilla
            # scan-fused block — speculation degrades to today's path
            k_eff = np.where(
                active, np.minimum(slots["k"], slots["remaining"] - 1),
                0).astype(np.int64)
            with self.stats.span("decode"):
                if k_eff.max() > 0:
                    self._spec_round(active, k_eff)
                else:
                    self._decode_tick(active)
        done = self._just_finished
        self._just_finished = []
        return done

    def _batch_bits(self):
        # submit() guarantees a RAGGED_PREFILL_FAMILIES family, all of
        # which support per-row bits — so budgets are always per-slot
        # (effective budgets were frozen at admission: a request's
        # configuration is stable for its lifetime even under the
        # closed-loop controller)
        budgets = shd.shard_budgets(
            jnp.asarray(self.slots["budget"], jnp.float32), self.mesh)  # (B,)
        wv, av = self.controller.resolve(budgets)
        if self.mesh is not None:
            wv, av = shd.shard_bits(wv, self.mesh), shd.shard_bits(av,
                                                                   self.mesh)
        return wv, av

    def _decode_tick(self, active) -> None:
        """Vanilla tick: one scan-fused decode block, per-row bits."""
        pool = self.pool
        slots = self.slots
        if self._recurrent:         # rows whose state the block carries
            self.stats.state_rows[self._tick] = int(active.sum())
        with self.stats.span("decode.dispatch"):
            wv, av = self._batch_bits()
            keys = self._split_key(self.decode_block)
            tok = jnp.asarray(slots["tok"][:, None], jnp.int32)
            t = jnp.asarray(slots["t"], jnp.int32)
            temp = jnp.asarray(slots["temp"], jnp.float32)
            topk = jnp.asarray(slots["topk"], jnp.int32)
            decode = (self._decode_scan_sh
                      if self._decode_scan_sh is not None
                      else self._decode_scan)
            tok, t, pool.cache, toks = decode(
                self.qparams, tok, t, pool.cache, wv, av, temp, topk, keys)
        # ONE coalesced device->host transfer per tick
        with self.stats.span("decode.sync"):
            tok_h, toks_h = jax.device_get((tok, toks))
        with self.stats.span("decode.harvest"):
            slots["tok"][:] = tok_h[:, 0].astype(np.int64)
            slots["t"][:] += self.decode_block
            for slot in np.nonzero(active)[0]:
                rid = int(slots.rid[slot])
                st = self.requests[rid]
                take = int(min(slots["remaining"][slot], self.decode_block))
                new = toks_h[slot, :take].tolist()
                if self.eos_id is not None and self.eos_id in new:
                    new = new[:new.index(self.eos_id) + 1]
                st.tokens.extend(int(x) for x in new)
                self.stats.tokens += len(new)
                slots["remaining"][slot] -= take
                hit_eos = (self.eos_id is not None and new
                           and new[-1] == self.eos_id)
                if slots["remaining"][slot] <= 0 or hit_eos:
                    self._finish(slot)

    def _spec_round(self, active, k_eff_h) -> None:
        """One speculative round for the whole batch: draft SPEC_K_MAX
        tokens per row at the LOW draft bits, verify the current token +
        all drafts in ONE (SPEC_K_MAX + 1)-wide chunked pass at each
        row's own target bits, deliver the longest accepted prefix + 1
        tokens, and mask the rejected KV entries
        (:meth:`repro.models.lm.CachePool.rollback`).  Rows with
        k_eff == 0 ride along and deliver exactly their one verified
        (target-bits) token — greedy output is bit-identical to the
        vanilla path either way."""
        pool = self.pool
        slots = self.slots
        with self.stats.span("decode.dispatch"):
            wv, av = self._batch_bits()
            dwv, dav = self._draft_bits()
            keys = self._split_key(SPEC_K_MAX + 2)
            tok = jnp.asarray(slots["tok"][:, None], jnp.int32)
            t = jnp.asarray(slots["t"], jnp.int32)
            temp = jnp.asarray(slots["temp"], jnp.float32)
            topk = jnp.asarray(slots["topk"], jnp.int32)
            k_eff = jnp.asarray(k_eff_h, jnp.int32)
            draft_toks, draft_probs, pool.cache = self._draft(
                self.qparams, tok, t, pool.cache, dwv, dav, temp, topk,
                keys[:SPEC_K_MAX])
            nxt, t_next, emitted, count, keep, pool.cache = self._verify(
                self.qparams, tok, draft_toks, draft_probs, t, pool.cache,
                wv, av, k_eff, temp, topk, keys[SPEC_K_MAX],
                keys[SPEC_K_MAX + 1])
            pool.rollback(keep)
        # ONE coalesced device->host transfer per round
        with self.stats.span("decode.sync"):
            nxt_h, t_next_h, emitted_h, count_h = jax.device_get(
                (nxt, t_next, emitted, count))
        with self.stats.span("decode.harvest"):
            slots["tok"][:] = nxt_h.astype(np.int64)
            slots["t"][:] = t_next_h.astype(np.int64)
            for slot in np.nonzero(active)[0]:
                rid = int(slots.rid[slot])
                st = self.requests[rid]
                take = int(count_h[slot])           # a + 1 <= remaining
                new = emitted_h[slot, :take].tolist()
                if self.eos_id is not None and self.eos_id in new:
                    new = new[:new.index(self.eos_id) + 1]
                st.tokens.extend(int(x) for x in new)
                self.stats.tokens += len(new)
                slots["remaining"][slot] -= take
                k_req = int(slots["k"][slot])
                if k_req > 0:
                    # honest per-round actuals at the REQUEST's chosen depth
                    # (clamped tail rounds still run/charge the full-width
                    # chunk; acceptance just can't use the tail)
                    st.spec_rounds += 1
                    st.draft_units += k_req
                    st.verify_units += k_req + 1
                    st.accepted_units += take - 1
                    st.spec_tokens += len(new)
                    st.draft_wbits = self._draft_wbits_f
                    if isinstance(self.controller, FluidController):
                        # close the draft-bit loop: this round's accept rate
                        # (accepted drafts over drafted) feeds the EMA that
                        # may shift the NEXT round's draft config
                        self.controller.observe_accept((take - 1) / k_req)
                hit_eos = (self.eos_id is not None and new
                           and new[-1] == self.eos_id)
                if slots["remaining"][slot] <= 0 or hit_eos:
                    self._finish(slot)


def _default_policy() -> PrecisionPolicy:
    from repro.core import policy as pol
    return pol.fixed(8)
