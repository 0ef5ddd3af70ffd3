"""DCRA task-routed MoE dispatch (the paper's technique as an LM feature).

Mapping (DESIGN.md §3): tokens = task invocations, experts = tiles owning
data, top-k routing = task spawning, expert capacity = IQ size (overflow is
dropped and carried by the residual — the paper's queue-overflow semantics),
and the dispatch all-to-all is the NoC. The *hierarchical* path performs a
two-stage all-to-all — intra-pod over the ``expert`` axis (tile-NoC), then
across pods over the ``pod`` axis (die-NoC) — the paper's §III-A two-level
torus: long-distance traffic is aggregated at a per-pod "portal", exactly
one die-NoC hop, instead of every tile talking across the package boundary.

Only the payload (x) and the local-expert id travel; source-slot and gate
metadata stay on the devices that need them for the return path, so the
collective bytes are the minimum the routing requires.

The bucketing / fused-payload all_to_all / pod-portal machinery lives in
:mod:`repro.core.routing` (shared with the distributed graph apps in
:mod:`repro.sparse.jax_apps`); this module keeps only what is MoE-specific:
the dispatch plan, the expert FFN, gating, and the return/combine path.
Rows enter each bucket by a gather through its slot ints. The expert and
pod-portal buckets give them back by a gather through each task's slot
(the bucket's own inverse map), so no row is scattered on the way back;
the combine at the source sums the returned rows per token with
``segment_sum``. All of it is differentiable, with one fused
``all_to_all`` per NoC stage under ``shard_map``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .compat import shard_map_unchecked
from .queues import QueueConfig
from .routing import (bucket as _bucket, fused_all_to_all, gather_rows,
                      noc_all_to_all as _a2a,
                      slot_scatter as _slot_scatter)


def dispatch_queues(moe_cfg) -> QueueConfig:
    """The MoE dispatch IQ sizing as a :class:`QueueConfig`.

    The ``capacity_factor`` knob IS the paper's IQ-size axis (Table II
    knob #8) — expressed here as relative ``iq_factors`` for the three
    bounded queues the dispatch routes through: the stage-1 tile-NoC
    bucket ("dispatch"), the stage-2 pod-portal bucket ("portal"), and the
    per-local-expert receive bucket ("expert"). ``moe_dcra`` resolves every
    bucket capacity with :meth:`QueueConfig.channel_cap` — the same path
    the graph apps and the analytic ``TaskEngine`` use.
    """
    return QueueConfig.for_moe_dispatch(moe_cfg.capacity_factor)


@dataclass(frozen=True)
class MeshInfo:
    mesh: Mesh
    data_axis: str = "data"
    expert_axis: str = "expert"
    tp_axis: str = "tp"
    pod_axis: Optional[str] = None       # set on the multi-pod mesh
    hierarchical: bool = True            # 2-stage a2a when experts span pods
    fsdp: bool = True                    # expert weights sharded over data
    fuse_tp: bool = True                 # fold tp into the expert group when
                                         # E divides (no psum, no seq gather)
    # (first, count): this mesh holds experts [first, first + count) of
    # the layer's E, split over its expert shards as the whole E would be;
    # tokens route over all E and only the held experts' tasks run here.
    # None: the mesh holds every expert.
    expert_share: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        from .fabric import Fabric
        if isinstance(self.mesh, Fabric):      # accept a Fabric transparently
            object.__setattr__(self, "mesh", self.mesh.mesh)

    def axis_size(self, name) -> int:
        from .fabric import Fabric
        if isinstance(name, list):
            name = tuple(name)
        return Fabric.of(self.mesh).axis_size(name)

    def all_axes(self) -> Tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    def dispatch_plan(self, num_experts: int):
        """How experts map onto the mesh — the packaging-time knob.

        Returns (group_axes_in_pod, spans_pods, tp_shards_ffn):
        * group_axes_in_pod: tuple of axes whose devices each own E/K experts
          (the stage-1 / tile-NoC all-to-all group);
        * spans_pods: stage-2 over the pod axis (die-NoC) is needed;
        * tp_shards_ffn: tp is NOT in the group -> expert FFN dim is
          tp-sharded (partial-F psum) and seq must be gathered over tp.
        """
        n_ex = self.axis_size(self.expert_axis)
        n_tp = self.axis_size(self.tp_axis)
        n_pod = self.axis_size(self.pod_axis)
        has_pod = self.pod_axis is not None and n_pod > 1
        cands = []
        if self.fuse_tp:
            if has_pod and self.hierarchical:
                cands.append(((self.expert_axis, self.tp_axis), True))
            cands.append(((self.expert_axis, self.tp_axis), False))
        if has_pod and self.hierarchical:
            cands.append(((self.expert_axis,), True))
        cands.append(((self.expert_axis,), False))
        for group, spans in cands:
            total = self.axis_size(group) * (n_pod if spans else 1)
            if num_experts % total == 0:
                return group, spans, self.tp_axis not in group
        return (self.expert_axis,), False, True


class SlotPlan(NamedTuple):
    """The slots one shard's dispatch allocates (see :func:`slot_plan`)."""
    tasks: int              # routed tasks the held experts expect:
                            # tokens x top-k x held / E
    dispatch_slots: int     # stage-1 bucket: group size x cap1
    expert_slots: int       # rows the expert FFN runs over: E_local x cap_e
    cap1: int               # stage-1 (tile-NoC) capacity per destination
    cap2: Optional[int]     # stage-2 (pod portal) capacity; None on one pod
    cap_e: int              # rows per local expert

    @property
    def slot_fill(self) -> float:
        """Held tasks over allocated expert slots: 1 when no slot is
        padding; capacity factor ``f`` compounds to about ``1 / f**2``
        on one shard, where dispatch and expert buckets both pad."""
        return self.tasks / self.expert_slots


def slot_plan(mc, info: MeshInfo, tokens: int,
              queues: Optional[QueueConfig] = None) -> SlotPlan:
    """Bucket sizes of :func:`moe_dcra` for ``tokens`` tokens on one shard
    of ``info``'s mesh — the numbers ``moe_dcra`` sizes its buckets with.

    ``mc`` is the model's MoE config; ``queues`` defaults to
    :func:`dispatch_queues`. The stage-1 bucket holds ``cap1`` tasks per
    rank of the dispatch group; what a shard receives (``cap1`` per
    group rank, or ``cap2`` per pod when experts span pods) is bucketed
    again by local expert into ``cap_e`` rows each. With an expert share
    (``info.expert_share``) the buckets are sized for the tasks the held
    experts expect, ``tokens * top_k * held / E``. Host-side and static:
    callers count slot fill without tracing the layer.
    """
    if queues is None:
        queues = dispatch_queues(mc)
    E = mc.num_experts
    _, held = info.expert_share or (0, E)
    group, spans_pods, _ = info.dispatch_plan(held)
    n_ex = info.axis_size(group)
    n_pod = info.axis_size(info.pod_axis) if spans_pods else 1
    e_local = held // (n_ex * n_pod)
    tasks = -(-tokens * mc.top_k * held // E)
    cap1 = queues.channel_cap("dispatch", tasks, n_ex)
    cap2 = None
    received = n_ex * cap1
    if spans_pods:
        cap2 = queues.channel_cap("portal", received, n_pod)
        received = n_pod * cap2
    cap_e = (received if e_local == 1
             else queues.channel_cap("expert", received, e_local))
    return SlotPlan(tasks=tasks, dispatch_slots=n_ex * cap1,
                    expert_slots=e_local * cap_e, cap1=cap1, cap2=cap2,
                    cap_e=cap_e)


def _expert_ffn(xe, wg, wu, wd, tp_axis, n_tp):
    """xe [E_l, C, D]; wg/wu [E_l, D, F_l]; wd [E_l, F_l, D] -> [E_l, C, D]."""
    dt = xe.dtype
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg.astype(dt))) * \
        jnp.einsum("ecd,edf->ecf", xe, wu.astype(dt))
    y = jnp.einsum("ecf,efd->ecd", h, wd.astype(dt))
    if n_tp > 1:
        y = jax.lax.psum(y, tp_axis)   # F is tp-sharded -> partial sums
    return y


def _group_limit(choice, n_group: int, topk_group: int):
    """``choice`` [..., E] with the experts outside each token's
    ``topk_group`` best of ``n_group`` equal groups set to -inf. A group's
    score is the sum of its two best choice scores (DeepSeek-V3)."""
    *lead, E = choice.shape
    grouped = choice.reshape(*lead, n_group, E // n_group)
    group_score = jax.lax.top_k(grouped, min(2, E // n_group))[0].sum(-1)
    _, kept = jax.lax.top_k(group_score, topk_group)
    keep = jnp.any(kept[..., :, None] == jnp.arange(n_group), axis=-2)
    return jnp.where(keep[..., None], grouped, -jnp.inf).reshape(choice.shape)


def gate(logits, mc, bias=None):
    """The router's choice from its float32 logits [..., E].

    Returns ``(gates [..., K], eids [..., K], probs [..., E])``: each
    token's top-k experts, their weights, and the per-expert scores the
    load-balance loss averages. ``mc.scoring`` picks the rule:

    * ``softmax``: the top-k of the softmax;
    * ``sigmoid`` (DeepSeek-V3's ``noaux_tc``): sigmoid scores; ``bias``
      [E] (``e_score_correction_bias``) is added to them only to choose.
      With ``n_group`` > 1 the choice is limited to each token's
      ``topk_group`` best groups (:func:`_group_limit`); the gates are the
      chosen experts' unbiased scores. Experts of other groups are masked
      to -inf, where DeepSeek-V3's reference code writes 0.0: a masked
      expert is then never chosen, even over a negative biased score.

    Either way the k gates are renormalised to sum to 1 and multiplied by
    ``mc.routed_scaling_factor``.
    """
    if mc.scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eids = jax.lax.top_k(probs, mc.top_k)
    elif mc.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores if bias is None else scores + bias
        if mc.n_group > 1:
            choice = _group_limit(choice, mc.n_group, mc.topk_group)
        _, eids = jax.lax.top_k(choice, mc.top_k)
        gates = jnp.take_along_axis(scores, eids, axis=-1)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        raise ValueError(f"unknown MoE scoring {mc.scoring!r}")
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if mc.routed_scaling_factor != 1.0:
        gates = gates * mc.routed_scaling_factor
    return gates, eids, probs


def shared_expert(params, x):
    """The shared experts, one SwiGLU over every token: x [..., D] ->
    [..., D] in x's dtype (weights ``shared_wg``/``shared_wu`` [D, Fs] and
    ``shared_wd`` [Fs, D])."""
    dt = x.dtype
    with jax.named_scope("dcra.moe.shared"):
        h = jax.nn.silu(x @ params["shared_wg"].astype(dt)) * \
            (x @ params["shared_wu"].astype(dt))
        return h @ params["shared_wd"].astype(dt)


def moe_dcra(params, x, cfg, info: MeshInfo,
             queues: Optional[QueueConfig] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """DCRA owner-routed dispatch. x [B, S, D] -> (out [B,S,D], aux []).

    ``queues`` overrides the dispatch queue sizing; the default derives it
    from ``cfg.moe.capacity_factor`` via :func:`dispatch_queues` (a
    ``DesignPoint.moe_queues()`` plugs in here for DSE sweeps). Bucket
    sizes come from :func:`slot_plan`.

    Tokens route over all ``E`` experts (:func:`gate`; ``router_bias``
    in ``params`` is the sigmoid router's selection bias). With
    ``info.expert_share`` the mesh holds only those experts: ``wg``/``wu``
    /``wd`` are theirs, the other experts' tasks are not dispatched, and
    the output is the held experts' part of the routed sum. With
    ``mc.n_shared`` the shared experts (:func:`shared_expert`) are added
    to it.

    The layer's device work runs under six scopes: ``dcra.moe.router``
    (logits, top-k, gates), ``dcra.moe.dispatch`` (stage buckets, token
    gather, collectives), ``dcra.moe.expert_pad`` (the per-expert
    bucket: a row gather into it, and a row gather back out through each
    received row's slot), ``dcra.moe.expert_ffn``,
    ``dcra.moe.combine`` (return path, gate-weighted sum, aux loss) and
    ``dcra.moe.shared``.
    """
    mc = cfg.moe
    assert mc is not None
    if queues is None:
        queues = dispatch_queues(mc)
    E = mc.num_experts
    first, held = info.expert_share or (0, E)
    group, spans_pods, tp_ffn = info.dispatch_plan(held)
    n_group = info.axis_size(group)
    n_pod = info.axis_size(info.pod_axis) if spans_pods else 1
    n_ex = n_group
    E_local = held // (n_group * n_pod)
    n_tp = info.axis_size(info.tp_axis) if tp_ffn else 1

    batch_ax = ((info.pod_axis, info.data_axis) if info.pod_axis
                else info.data_axis)

    def _div(n, ax):
        return ax is not None and n % info.axis_size(ax) == 0

    b_in, s_in, _ = x.shape
    if not _div(b_in, batch_ax):       # tiny-batch decode fallbacks
        batch_ax = info.data_axis if _div(b_in, info.data_axis) else None
    # Preferred: seq sharded over the WHOLE dispatch group (+tp when the
    # FFN is tp-split) — tokens arrive distinct per shard, no pre-gather,
    # no slice (the residual stream is already seq-sharded this way by SP).
    grp = tuple(group) if isinstance(group, tuple) else (group,)
    seq_group = grp + ((info.tp_axis,) if tp_ffn else ())
    if _div(s_in, seq_group):
        seq_ax, seq_mode = seq_group, "group"
    elif _div(s_in, info.tp_axis) and info.axis_size(info.tp_axis) > 1:
        seq_ax, seq_mode = info.tp_axis, "tp"
    else:
        seq_ax, seq_mode = None, None
    x_spec = P(batch_ax, seq_ax, None)
    e_dim = ((info.pod_axis,) + tuple(group) if spans_pods else
             (group if isinstance(group, tuple) else (group,)))
    e_dim = e_dim[0] if len(e_dim) == 1 else e_dim
    f_axis = info.tp_axis if tp_ffn else None
    d_axis = info.data_axis if info.fsdp else None
    w_specs = (P(None, None),                 # router (replicated)
               P(e_dim, d_axis, f_axis),      # wg
               P(e_dim, d_axis, f_axis),      # wu
               P(e_dim, f_axis, d_axis))      # wd

    def kernel(router, wg, wu, wd, xb, *bias):
        s_shard = xb.shape[1]
        tp_gather = tp_ffn and n_tp > 1 and seq_mode is not None
        if tp_gather:
            # FFN is tp-split on F (partial psum): every tp rank must hold
            # the same tokens -> gather the seq shards.
            xb = jax.lax.all_gather(xb, info.tp_axis, axis=1, tiled=True)
        b_l, s_l, D = xb.shape
        T_l = b_l * s_l
        xf = xb.reshape(T_l, D)
        # In "group" seq mode tokens are already distinct per expert-rank.
        # Otherwise the residual stream is REPLICATED over the expert axis
        # (it serves as a TP axis for dense layers) — each expert-rank then
        # dispatches only its 1/n_ex slice and the output is re-gathered.
        n_slice = info.axis_size(info.expert_axis)
        do_slice = (seq_mode != "group" and n_slice > 1
                    and T_l % n_slice == 0)
        if do_slice:
            e_i = jax.lax.axis_index(info.expert_axis)
            T_l = T_l // n_slice
            xf = jax.lax.dynamic_slice_in_dim(xf, e_i * T_l, T_l, 0)
        if info.fsdp:
            wg = jax.lax.all_gather(wg, info.data_axis, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, info.data_axis, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, info.data_axis, axis=2, tiled=True)

        # --- routing (task spawning) -----------------------------------
        with jax.named_scope("dcra.moe.router"):
            logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                                router.astype(jnp.float32))
            gates, eids, probs = gate(logits, mc, *bias)    # [T_l, K]
            K = mc.top_k
            eids_f = eids.reshape(-1)
            gates_f = gates.reshape(-1).astype(jnp.float32)
            src_f = jnp.repeat(jnp.arange(T_l, dtype=jnp.int32), K)
        plan = slot_plan(mc, info, T_l, queues)

        with jax.named_scope("dcra.moe.dispatch"):
            if held == E:
                local = eids_f
                owner = local // E_local                    # global shard id
                valid = jnp.ones_like(eids_f, dtype=bool)
            else:   # tasks of experts held elsewhere stay undispatched
                local = eids_f - first
                valid = (local >= 0) & (local < held)
                local = jnp.where(valid, local, 0)
                owner = local // E_local
            if not spans_pods:
                # ---- single-stage fused a2a (tile-NoC) -----------------
                _, (eid1, tok1), slot_of_task, _ = _bucket(
                    src_f[:, None] * 0, owner, valid,
                    [local % E_local, src_f], n_ex, plan.cap1)
                xb1 = gather_rows(xf, tok1)
                xr, (eidr,) = fused_all_to_all(xb1, [eid1], group)
            else:
                # ---- stage 1 over expert axis (tile-NoC) ---------------
                e_coord = owner % n_ex
                p_coord = owner // n_ex
                _, (pc1, eid1, tok1), slot_of_task, _ = _bucket(
                    src_f[:, None] * 0, e_coord, valid,
                    [p_coord, local % E_local, src_f], n_ex, plan.cap1)
                xb1 = gather_rows(xf, tok1)
                xs1, (pcs, eids1) = fused_all_to_all(xb1, [pc1, eid1], group)
                n1 = xs1.shape[0]
                # ---- stage 2 over pod axis (die-NoC portal) ------------
                valid1 = pcs >= 0
                _, (eid2, slot1_of_s2), slot2_of_s1, _ = _bucket(
                    pcs[:, None] * 0, jnp.maximum(pcs, 0), valid1,
                    [eids1, jnp.arange(n1, dtype=jnp.int32)], n_pod,
                    plan.cap2)
                xb2 = gather_rows(xs1, slot1_of_s2)
                xr, (eidr,) = fused_all_to_all(xb2, [eid2], info.pod_axis)

        # --- local expert execution (owner computes) --------------------
        N_r = xr.shape[0]
        validr = eidr >= 0
        if E_local == 1:
            with jax.named_scope("dcra.moe.expert_ffn"):
                ye = _expert_ffn(xr[None].astype(xb.dtype), wg, wu, wd,
                                 info.tp_axis, n_tp)[0]
                ye = ye * validr[:, None].astype(ye.dtype)
        else:
            cap_e = plan.cap_e
            with jax.named_scope("dcra.moe.expert_pad"):
                # second-level IQ: bucket received tasks by local expert
                _, (srce,), slot_of_r, _ = _bucket(
                    validr[:, None].astype(jnp.int32) * 0,
                    jnp.maximum(eidr, 0), validr,
                    [jnp.arange(N_r, dtype=jnp.int32)], E_local, cap_e)
                xe = gather_rows(xr, srce).reshape(E_local, cap_e, D)
            with jax.named_scope("dcra.moe.expert_ffn"):
                ye_b = _expert_ffn(xe.astype(xb.dtype), wg, wu, wd,
                                   info.tp_axis, n_tp)
            with jax.named_scope("dcra.moe.expert_pad"):
                # back to received order: each row reads its own slot
                ye = gather_rows(ye_b.reshape(E_local * cap_e, D), slot_of_r)

        with jax.named_scope("dcra.moe.combine"):
            # --- return path (retrace the NoC route) --------------------
            if not spans_pods:
                yb1 = _a2a(ye, group)
            else:
                y2 = _a2a(ye, info.pod_axis)                # back to portal
                y1 = gather_rows(y2, slot2_of_s1)
                yb1 = _a2a(y1, group)                # back to source

            # combine at the source, weighted by gate: read the fewer of
            # the tasks and the returned slots (an expert share leaves
            # most tasks undispatched)
            if plan.dispatch_slots < T_l * K:
                n_slots = yb1.shape[0]
                kept = slot_of_task >= 0
                gate1 = _slot_scatter(gates_f, jnp.maximum(slot_of_task, 0),
                                      kept, n_slots)
                out = jax.ops.segment_sum(
                    yb1.astype(jnp.float32) * gate1[:, None],
                    jnp.maximum(tok1, 0), num_segments=T_l)
            else:
                task_y = jnp.where(
                    (slot_of_task >= 0)[:, None],
                    yb1[jnp.maximum(slot_of_task, 0)],
                    0.0).astype(jnp.float32)
                out = jax.ops.segment_sum(task_y * gates_f[:, None], src_f,
                                          num_segments=T_l)

            # aux: load-balance loss, averaged over all devices
            frac = jax.nn.one_hot(eids, E, dtype=jnp.float32).sum(1).mean(0)
            aux = E * jnp.sum(frac * probs.mean(0))
            aux = jax.lax.pmean(aux, info.all_axes())
            if do_slice:   # restore the expert-replicated layout
                out = jax.lax.all_gather(out, info.expert_axis, axis=0,
                                         tiled=True)
            out = out.reshape(b_l, s_l, D).astype(x.dtype)
            if tp_gather:   # slice back this rank's seq shard
                tp_i = jax.lax.axis_index(info.tp_axis)
                out = jax.lax.dynamic_slice_in_dim(out, tp_i * s_shard,
                                                   s_shard, axis=1)
        return out, aux

    bias = ((params["router_bias"],) if "router_bias" in params else ())
    fn = shard_map_unchecked(kernel, mesh=info.mesh,
                             in_specs=(*w_specs, x_spec) + (P(None),) * len(bias),
                             out_specs=(x_spec, P()))
    out, aux = fn(params["router"], params["wg"], params["wu"], params["wd"],
                  x, *bias)
    if mc.n_shared:
        out = out + shared_expert(params, x)
    return out, aux
