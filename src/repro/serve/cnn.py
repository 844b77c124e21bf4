"""Batched CNN image serving: the batched-forward workload adapter.

The CNN analogue of :class:`repro.serve.engine.ServeEngine` (DESIGN.md
§7/§8): weights are quantized/prepacked ONCE at engine construction
(``cnn.quantize_cnn_params`` — int8 containers, packed int4 where the
controller's configurations make a layer eligible), and ONE compiled
forward serves every batch: each image's latency/EDP budget resolves
through a :class:`repro.core.policy.BudgetController` (or closed-loop
:class:`~repro.core.policy.FluidController`, charged image by image)
into a per-layer bit vector, the batch's ``(B, n_gemm)`` bit *matrix*
is an ordinary traced input executed via the bit-grouped batch dispatch
(``kernels/ops.py``), and the whole batch's resolved matrix is priced
in one pass through the paper's calibrated AP cost model
(``apsim.metrics.price_bit_matrix``) — per-request AP
latency/energy/EDP come back with the logits (Table VII, live per
image).  Queue/scheduler/stats/pricing plumbing lives in the shared
:class:`repro.serve.runtime.ServeRuntime`.

Batches pad to a fixed ``max_batch`` so batch-size churn never
retraces; ``stats.forward_traces`` proves the zero-retrace property.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import dist
from repro.apsim import metrics as apm
from repro.apsim.workloads import Layer, gemm_layers
from repro.core.policy import BudgetController, PrecisionPolicy, fixed
from repro.dist import sharding as shd
from repro.models import cnn
from repro.serve.accounting import ImageStats, RuntimeStats  # noqa: F401
from repro.serve.runtime import ServeRuntime


class CNNServeEngine(ServeRuntime):
    """Batched, bit-fluid CNN inference server.

    ``serve(images, budgets)`` runs one batch: ``images`` (B, H, W, C)
    with B <= ``max_batch`` (short batches right-pad; padded rows take
    the cheapest configuration and are dropped from the results), and
    ``budgets`` a scalar or ``(B,)`` per-image vector on the
    controller's budget axis (EDP by default — see
    ``policy.cnn_budget_controller``; ``None`` = unconstrained = most
    accurate configuration).  Returns ``(logits (B, num_classes),
    [ImageStats])``.
    """

    def __init__(self, params: dict, layers: Sequence[Layer], *,
                 controller: Optional[BudgetController] = None,
                 policy: Optional[PrecisionPolicy] = None,
                 max_batch: int = 8, container: str = "auto", mesh=None,
                 plan=None):
        self.layers = list(layers)
        gl = gemm_layers(self.layers)
        self.n_gemm = len(gl)
        if controller is None:
            pol = policy or fixed(8)
            controller = BudgetController({pol.name: pol}, {pol.name: 0.0},
                                          self.n_gemm)
        if plan == "auto":
            # resolve here rather than in the runtime: a CNN plan needs
            # the per-layer NAMES so replicates() can match the
            # per-layer-keyed qparams dicts (true LRMP-style per-layer
            # replication — LM stacks can't differentiate layers)
            m = mesh if mesh is not None else dist.active_mesh()
            nd = dist.placement.mesh_device_count(m)
            plan = (dist.placement.plan_for_controller(
                        controller, apm.network_gemms(self.layers),
                        n_devices=nd, names=tuple(l.name for l in gl))
                    if nd > 1 else None)
        super().__init__(controller, self.n_gemm,
                         gemms=apm.network_gemms(self.layers), mesh=mesh,
                         plan=plan, slot_desc="GEMM (conv/fc) layers")
        self.max_batch = max_batch
        wtab, _ = controller.stacked_tables()
        if container == "auto":
            int4_names = cnn.int4_eligible(self.layers, wtab)
            container = "int8"
        else:
            int4_names = ()
            wmax = int(np.max(np.asarray(wtab)))
            if container == "int4" and wmax > 4:
                raise ValueError(
                    f"container='int4' caps fidelity at 4 bits but the "
                    f"controller can resolve up to {wmax}-bit "
                    f"configurations — requests would be priced at a "
                    f"precision the container cannot honor (use "
                    f"container='auto' to pack int4 only where every "
                    f"configuration stays <= 4 bits)")
        self.int4_names = int4_names
        self.qparams = cnn.quantize_cnn_params(params, self.layers,
                                               container=container,
                                               int4_names=int4_names)
        if self.mesh is not None:       # place serve weights once — the
            # plan's fully-replicated layers override the base rules
            self.qparams = jax.device_put(
                self.qparams, shd.param_shardings(self.qparams, self.mesh,
                                                  plan=self.plan))
        # scale-out execution gate (mirrors ServeEngine): a fully-
        # replicated plan runs the batched forward under shard_map with
        # image ROWS split across dp — rows are independent, so the
        # per-device compute is exact
        self._dp_exec = None
        if (self.plan is not None and self.mesh is not None
                and self.plan.fully_replicated):
            dpx = dist.mesh_axes_for(self.mesh, "dp")
            dp = dist.dp_size(self.mesh)
            if dpx and dp > 1 and max_batch % dp == 0:
                self._dp_exec = dpx[0] if len(dpx) == 1 else tuple(dpx)

        def _fwd(qp, x, wmat, amat):
            self.stats.trace("forward")
            return cnn.cnn_forward(qp, x, self.layers, wmat, amat)

        if self._dp_exec is not None:
            from jax.sharding import PartitionSpec as P

            dpx = self._dp_exec

            def _fwd_manual(qp, x, wmat, amat):
                with dist.manual_mode():
                    return _fwd(qp, x, wmat, amat)

            self._fwd = jax.jit(jax.shard_map(
                _fwd_manual, mesh=self.mesh,
                in_specs=(P(), P(dpx, None, None, None),
                          P(dpx, None), P(dpx, None)),
                out_specs=P(dpx, None), check_vma=False))
        else:
            self._fwd = jax.jit(_fwd)

    def serve(self, images, budgets=None
              ) -> Tuple[np.ndarray, List[ImageStats]]:
        """One batched inference; see class docstring."""
        images = jnp.asarray(images)
        B = images.shape[0]
        if not 1 <= B <= self.max_batch:
            raise ValueError(f"batch of {B} images exceeds max_batch="
                             f"{self.max_batch}")
        submitted = time.time()
        if budgets is None:
            req: List[Optional[float]] = [None] * B
        else:
            req = np.broadcast_to(np.asarray(budgets, np.float64),
                                  (B,)).tolist()
        # batch admission planning: closed-loop controllers are charged
        # image by image, so effective budgets tighten within the batch
        bud = self.plan_admissions(req)
        # pad to the fixed batch shape: padded rows take the cheapest
        # configuration (budget 0 fits nothing -> fastest) and are dropped
        pad = self.max_batch - B
        if pad:
            images = jnp.pad(images, ((0, pad),) + ((0, 0),) * 3)
            bud = np.concatenate([bud, np.zeros((pad,), np.float64)])
        budv = shd.shard_budgets(jnp.asarray(bud, jnp.float32), self.mesh)
        wmat, amat = self.controller.resolve(budv)
        if self.mesh is not None:
            images = shd.shard_batch({"x": images}, self.mesh)["x"]
            wmat = shd.shard_bits(wmat, self.mesh)
            amat = shd.shard_bits(amat, self.mesh)
        with self.compute_ctx():
            logits = self._fwd(self.qparams, images, wmat, amat)
        # ONE coalesced device->host transfer per batch
        wmat_h, amat_h, logits_h = jax.device_get((wmat, amat, logits))
        wmat_h = wmat_h.astype(np.int64)[:B]
        amat_h = amat_h.astype(np.int64)[:B]
        costs = self.price_matrix_bits(wmat_h, amat_h)     # one-pass batch
        replicas = (self.plan.mean_replicas if self.plan is not None
                    else 0.0)
        stats = []
        for i in range(B):
            rec = ImageStats(
                rid=self.next_rid(), budget_s=float(bud[i]), index=i,
                mean_wbits=float(np.mean(wmat_h[i])), ap_cost=costs[i],
                wbits=tuple(int(b) for b in wmat_h[i]),
                abits=tuple(int(b) for b in amat_h[i]),
                plan_replicas=replicas,
                submitted_s=submitted)
            self.requests[rec.rid] = rec
            self.finish_record(rec.rid)
            stats.append(rec)
        self.stats.admitted += B
        self.stats.images += B
        return logits_h[:B], stats


def hawq_fidelity_sweep(network: str = "resnet18", image: int = 32,
                        batch: int = 2, seed: int = 0
                        ) -> Tuple[Dict[str, float], int]:
    """Run every ``HAWQV3_RESNET18`` configuration through the serve-form
    kernels in ONE compiled program; returns ``({constraint:
    fidelity-vs-fp}, n_traces)``.

    Fidelity is softmax total-variation agreement with the fp
    (fake-quant-identity) reference — the functional accuracy axis of
    the Table VII accuracy-vs-EDP reproduction.  ``n_traces`` counts
    compiles across all five configuration switches; 1 is the
    zero-retrace claim (``paper/table7_bitfluid.py`` gates on it,
    ``examples/mixed_precision_resnet18.py`` prints it).
    """
    from repro.apsim.workloads import HAWQV3_RESNET18, per_layer_bits

    key = jax.random.PRNGKey(seed)
    params, layers = cnn.init_cnn(network, key, image=image)
    qp = cnn.quantize_cnn_params(params, layers)
    x = jax.random.normal(key, (batch, image, image, 3), jnp.float32)
    ref = jax.nn.softmax(cnn.cnn_forward(params, x, layers), axis=-1)
    traces: List[int] = []

    def fwd(wv):
        traces.append(1)
        return cnn.cnn_forward(qp, x, layers, wv, wv)

    jfwd = jax.jit(fwd)
    fid = {}
    for name, vec in HAWQV3_RESNET18.items():
        bits = jnp.asarray(per_layer_bits(layers, vec), jnp.int32)
        out = jax.nn.softmax(jfwd(bits), axis=-1)
        fid[name] = float(1.0 - 0.5 * jnp.abs(out - ref).sum(-1).mean())
    assert all(np.isfinite(v) for v in fid.values())
    return fid, len(traces)
