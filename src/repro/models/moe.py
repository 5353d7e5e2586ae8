"""Mixture-of-Experts FFN with capacity-based dispatch + expert parallelism.

Routing is top-k over a learned router; dispatch is the sort-based
"dropped-token" scheme (Megablocks/Switch style) with static shapes:

  1. expand tokens × top-k hits, stable-sort by expert id;
  2. slot = rank within the expert group (cummax trick); hits beyond the
     per-expert ``capacity`` are dropped;
  3. scatter into an (E, C, D) buffer, run all experts as one batched
     einsum (MXU-friendly), gather back with gate weighting.

Compiled FLOPs therefore scale with ``tokens × top_k × capacity_factor`` —
NOT ``tokens × n_experts`` — which keeps the §Roofline
``MODEL_FLOPS/HLO_FLOPs`` ratio honest.

**Expert parallelism**: inside a mesh context the FFN runs under
``shard_map``; experts are sharded over the ``model`` axis, every rank
dispatches only the hits of its local experts (activations are replicated
across ``model`` between layers, so no all-to-all is needed on the way in),
and the combine is a single ``psum`` over ``model`` — the same collective a
tensor-parallel dense FFN would issue.  Without a mesh the same code runs
single-device (smoke tests).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_mesh, shard
from repro.models.layers import make_param


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    d_ff_expert: int = 1024
    n_shared: int = 0            # always-on shared experts (DeepSeek)
    capacity_factor: float = 1.25
    aux_coef: float = 0.01       # load-balance loss coefficient
    z_coef: float = 1e-3         # router z-loss
    moe_every: int = 1           # FFN is MoE on layers where idx % moe_every == 0
    first_dense: bool = False    # layer 0 uses a dense FFN (DeepSeek-V2)
    use_shard_map: bool = False  # manual EP over 'model' (psum combine).
                                 # Preferred on TPU; default off because
                                 # XLA-CPU's AllReducePromotion pass crashes
                                 # on the emitted reducer (DESIGN.md §2).
    dispatch_groups: int = 0     # §Perf: >0 = dp-grouped dispatch — sort/
                                 # capacity computed per data-shard group so
                                 # no token array crosses the data axis
                                 # (kills the global-sort all-gathers).


def init_moe(key: jax.Array, cfg) -> Dict[str, Any]:
    m: MoEConfig = cfg.moe
    d, e, f = cfg.d_model, m.n_experts, m.d_ff_expert
    ks = jax.random.split(key, 7)
    p = {
        "w_router": make_param(ks[0], (d, e), ("embed", "experts"), jnp.float32),
        "w_gate": make_param(ks[1], (e, d, f), ("experts", "embed", "expert_mlp"), cfg.np_dtype),
        "w_up": make_param(ks[2], (e, d, f), ("experts", "embed", "expert_mlp"), cfg.np_dtype),
        "w_down": make_param(
            ks[3], (e, f, d), ("experts", "expert_mlp", "embed"), cfg.np_dtype,
            scale=f ** -0.5,
        ),
    }
    if m.n_shared:
        fs = m.n_shared * f
        p["shared"] = {
            "w_gate": make_param(ks[4], (d, fs), ("embed", "mlp"), cfg.np_dtype),
            "w_up": make_param(ks[5], (d, fs), ("embed", "mlp"), cfg.np_dtype),
            "w_down": make_param(ks[6], (fs, d), ("mlp", "embed"), cfg.np_dtype, scale=fs ** -0.5),
        }
    return p


def _route(x32: jax.Array, w_router: jax.Array, top_k: int):
    """Returns (gates (N,k), experts (N,k), aux losses). x32: (N, D) f32."""
    logits = x32 @ w_router                       # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)  # (N, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss + z-loss.
    e = w_router.shape[1]
    density = jnp.mean(
        jax.nn.one_hot(experts[:, 0], e, dtype=jnp.float32), axis=0
    )
    mean_prob = probs.mean(axis=0)
    aux = e * jnp.sum(density * mean_prob)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return gates.astype(jnp.float32), experts, aux, z


def _dispatch_ffn(
    x: jax.Array,          # (N, D) local tokens (model-replicated)
    gates: jax.Array,      # (N, k) f32
    experts: jax.Array,    # (N, k) int — GLOBAL expert ids
    w_gate: jax.Array,     # (E_local, D, F)
    w_up: jax.Array,
    w_down: jax.Array,
    e_offset: jax.Array,   # first global expert id owned locally
    capacity: int,
) -> jax.Array:
    """Sort-based dispatch → batched expert FFN → weighted combine."""
    n, k = experts.shape
    e_local = w_gate.shape[0]
    flat_e = experts.reshape(-1) - e_offset               # (N*k,)
    flat_gate = gates.reshape(-1)
    flat_src = jnp.repeat(jnp.arange(n), k)
    valid = (flat_e >= 0) & (flat_e < e_local)
    sort_key = jnp.where(valid, flat_e, e_local)          # invalid → sentinel
    order = jnp.argsort(sort_key, stable=True)
    s_e = sort_key[order]
    idx = jnp.arange(n * k)
    is_start = jnp.concatenate([jnp.ones((1,), bool), s_e[1:] != s_e[:-1]])
    group_start = jax.lax.cummax(jnp.where(is_start, idx, 0))
    slot = idx - group_start
    ok = (s_e < e_local) & (slot < capacity)
    dest = jnp.where(ok, s_e * capacity + slot, e_local * capacity)

    buf = jnp.zeros((e_local * capacity + 1, x.shape[-1]), x.dtype)
    buf = buf.at[dest].set(x[flat_src[order]], mode="drop")
    buf = buf[:-1].reshape(e_local, capacity, -1)         # (E_local, C, D)
    buf = shard(buf, "act_expert", None, None)

    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", buf, w_gate, preferred_element_type=jnp.float32)
    ).astype(x.dtype) * jnp.einsum("ecd,edf->ecf", buf, w_up)
    h = shard(h, "act_expert", None, None)
    out = jnp.einsum("ecf,efd->ecd", h, w_down)           # (E_local, C, D)

    out_rows = out.reshape(e_local * capacity, -1)
    picked = jnp.where(
        ok[:, None], out_rows[jnp.minimum(dest, e_local * capacity - 1)], 0.0
    )
    y = jnp.zeros_like(x, shape=(n, x.shape[-1]))
    y = y.at[flat_src[order]].add(
        picked * flat_gate[order][:, None].astype(x.dtype)
    )
    return y


def moe_forward(p: Dict, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,D) → (y, aux_loss).  EP over 'model' when a mesh is active."""
    m: MoEConfig = cfg.moe
    B, S, D = x.shape
    n = B * S
    capacity = max(8, int(n * m.top_k * m.capacity_factor / m.n_experts))
    mesh = current_mesh()

    def local(x_l, w_router, w_gate, w_up, w_down, e_offset):
        xf = x_l.reshape(-1, D)
        gates, experts, aux, z = _route(xf.astype(jnp.float32), w_router, m.top_k)
        cap = max(8, int(xf.shape[0] * m.top_k * m.capacity_factor / m.n_experts))
        y = _dispatch_ffn(
            xf, gates, experts, w_gate, w_up, w_down, e_offset, cap
        )
        return y.reshape(x_l.shape), aux + m.z_coef / max(m.aux_coef, 1e-9) * z

    if m.dispatch_groups and (mesh is None or not m.use_shard_map):
        # dp-grouped dispatch: tokens reshaped (G, n/G, D) with G sharded
        # over the data axes; sort, capacity and scatter are group-local, so
        # GSPMD never moves token arrays across `data` — only the expert
        # einsum and its combine cross `model`.
        G = m.dispatch_groups
        n_flat = B * S
        if n_flat % G != 0:
            raise ValueError(
                f"{n_flat} tokens do not split into dispatch_groups={G}"
            )
        xg = x.reshape(G, n_flat // G, D)
        xg = shard(xg, "batch", None, None)

        def group_fn(x_l):
            return local(
                x_l[None], p["w_router"], p["w_gate"], p["w_up"], p["w_down"],
                jnp.int32(0),
            )

        yg, auxg = jax.vmap(group_fn)(xg)
        y = shard(yg, "batch", None, None, None).reshape(B, S, D)
        aux = auxg.mean()
    elif mesh is None or "model" not in mesh.axis_names or not m.use_shard_map:
        # GSPMD path: experts sharded over `model` via the param specs and
        # the act_expert constraints on the dispatch buffers; GSPMD derives
        # the dispatch/combine collectives.
        y, aux = local(
            x, p["w_router"], p["w_gate"], p["w_up"], p["w_down"], jnp.int32(0)
        )
    else:
        n_model = mesh.shape["model"]
        if m.n_experts % n_model != 0:
            raise ValueError(
                f"{m.n_experts} experts do not shard over "
                f"model axis of {n_model}"
            )
        e_local = m.n_experts // n_model

        def ranked(x_l, w_router, w_gate, w_up, w_down):
            rank = jax.lax.axis_index("model")
            y, aux = local(x_l, w_router, w_gate, w_up, w_down, rank * e_local)
            # f32 combine: numerically safer for k-way partial sums, and
            # sidesteps XLA-CPU's bf16 AllReducePromotion crash.
            y = jax.lax.psum(y.astype(jnp.float32), "model").astype(x_l.dtype)
            aux = jax.lax.pmean(aux, "model")
            return y, aux

        # Only "model" goes manual; pod/data stay under GSPMD ("auto").
        # check_vma=True tracks replication properly — without it shard_map
        # emits a copy-reducer all-reduce that XLA-CPU's promotion pass
        # cannot clone for the bf16 cotangents.
        y, aux = jax.shard_map(
            ranked,
            mesh=mesh,
            axis_names={"model"},
            in_specs=(
                P(None, None, None),
                P(None, None),
                P("model", None, None),
                P("model", None, None),
                P("model", None, None),
            ),
            out_specs=(P(None, None, None), P()),
            check_vma=True,
        )(x, p["w_router"], p["w_gate"], p["w_up"], p["w_down"])

    if "shared" in p:
        sp = p["shared"]
        h = jax.nn.silu((x @ sp["w_gate"]).astype(jnp.float32)).astype(x.dtype) * (
            x @ sp["w_up"]
        )
        h = shard(h, "batch", "act_seq", "act_mlp")
        y = y + h @ sp["w_down"]
    return shard(y, "batch", "act_seq", "act_embed"), m.aux_coef * aux


def moe_forward_dense_ref(p: Dict, x: jax.Array, cfg) -> jax.Array:
    """Oracle: every expert computed for every token, exact soft combine with
    the same top-k gates (no capacity drops).  Used by tests to validate the
    dispatch path (with capacity_factor high enough that nothing drops)."""
    m: MoEConfig = cfg.moe
    B, S, D = x.shape
    xf = x.reshape(-1, D)
    gates, experts, _, _ = _route(xf.astype(jnp.float32), p["w_router"], m.top_k)
    h = jax.nn.silu(
        jnp.einsum("nd,edf->nef", xf, p["w_gate"], preferred_element_type=jnp.float32)
    ).astype(x.dtype) * jnp.einsum("nd,edf->nef", xf, p["w_up"])
    out_all = jnp.einsum("nef,efd->ned", h, p["w_down"])    # (N, E, D)
    sel = jnp.take_along_axis(out_all, experts[..., None], axis=1)  # (N, k, D)
    y = (sel * gates[..., None].astype(x.dtype)).sum(axis=1)
    if "shared" in p:
        sp = p["shared"]
        hs = jax.nn.silu((xf @ sp["w_gate"]).astype(jnp.float32)).astype(x.dtype) * (
            xf @ sp["w_up"]
        )
        y = y + hs @ sp["w_down"]
    return y.reshape(B, S, D)
