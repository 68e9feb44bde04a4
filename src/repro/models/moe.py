"""Mixture-of-Experts FFN (Mixtral 8×top-2, Granite 40e×top-8).

Two execution paths:

* **shard_map path** (training under a mesh) — the production path.  XLA's
  automatic partitioner replicates the vmapped dispatch gather/scatter
  buffers and contraction-shards the expert matmuls (measured on
  mixtral-8x22b train: 4.5 TB/device of all-reduce + 1.2 TB of replicated
  scatter-add per step — EXPERIMENTS.md §Perf iteration 2).  shard_map makes
  the intent explicit instead:

      - tokens stay on their data shard (dispatch is 100 % local — the
        paper's "no data rearrangement" discipline applied to routing);
      - expert weights are TP-sharded over ``model`` on the hidden axis and
        FSDP-sharded over ``data``; the data shards are all-gathered once
        per layer (the ZeRO-3 gather), its transpose is the grads'
        reduce-scatter;
      - gate/up are column-parallel, down is row-parallel with one psum —
        exactly Megatron discipline, two collectives per MoE layer.

* **local path** (no mesh / quantized serving) — plain vmapped dispatch;
  also the numerical oracle the shard_map path is tested against.

Dispatch is capacity-based per group (= per sequence): C = ceil(S · top_k /
E · capacity_factor), overflow drops to a trash row.  Router in f32 +
GShard load-balance aux loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.quant import QuantizedTensor
from repro.core.sparsity import SparseQuantizedTensor
from repro.kernels import ops
from repro.models.layers import Params, dense_init, linear
from repro.parallel.hints import active_mesh


def moe_init(key, cfg) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    scale = 1.0 / jnp.sqrt(d)
    return {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale).astype(cfg.dtype),
        "up": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * scale).astype(cfg.dtype),
        "down": (jax.random.normal(ks[3], (e, f, d), jnp.float32) / jnp.sqrt(f)).astype(cfg.dtype),
    }


def capacity(cfg, tokens_per_group: int) -> int:
    c = int(tokens_per_group * cfg.top_k / cfg.n_experts * cfg.moe_capacity_factor)
    return max(cfg.top_k, min(c, tokens_per_group))


# ---------------------------------------------------------------------------
# routing + dispatch (local to one shard / one process)
# ---------------------------------------------------------------------------

def _route(cfg, router, x):
    """x (B, S, d) -> (topw, topi (B, S, k), me, ce).

    me/ce are the per-expert mean prob / token fraction (GShard aux terms),
    returned unreduced so the shard_map path can pmean them across shards
    BEFORE the product (exact global aux, not a mean-of-products)."""
    e, k = cfg.n_experts, cfg.top_k
    bsz, seq, _ = x.shape
    # router matmul in the compute dtype (a f32 matmul here would inject a
    # f32 dx psum per layer — §Perf it.4); softmax statistics in f32
    logits = linear(x, router.astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(axis=(0, 1))
    ce = jnp.zeros((e,), jnp.float32).at[topi.reshape(-1)].add(
        jnp.ones((bsz * seq * k,), jnp.float32)) / (bsz * seq * k)
    return topw, topi, me, ce


def _aux_loss(cfg, me, ce):
    return cfg.n_experts * jnp.sum(me * ce)


def _dispatch_compute(cfg, x, topi, topw, expert_fn):
    """Group-local gather dispatch.  x (B, S, d); expert_fn maps
    (E, C, d) -> (E, C, d_out)."""
    bsz, seq, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = capacity(cfg, seq)

    def group(xg, ig, wg):
        flat_e = ig.reshape(-1)                               # (S*k,)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - onehot)
        pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        keep = pos < cap
        dst = jnp.where(keep, flat_e * cap + pos, e * cap)
        src = jnp.repeat(jnp.arange(seq), k)
        buf = jnp.zeros((e * cap + 1, d), xg.dtype).at[dst].set(xg[src])
        return buf[: e * cap].reshape(e, cap, d), (dst, src, keep, wg)

    hidden, meta = jax.vmap(group)(x, topi, topw)             # (B, E, C, d)
    out_e = expert_fn(hidden)                                  # (B, E, C, d_out)
    d_out = out_e.shape[-1]

    def combine(oe, m):
        dst, src, keep, wg = m
        flat = jnp.concatenate(
            [oe.reshape(-1, d_out), jnp.zeros((1, d_out), oe.dtype)])
        gathered = flat[dst] * (wg.reshape(-1)[:, None] *
                                keep[:, None]).astype(oe.dtype)
        return jnp.zeros((x.shape[1], d_out), oe.dtype).at[src].add(gathered)

    return jax.vmap(combine)(out_e, meta)


# ---------------------------------------------------------------------------
# local (single-shard / quantized-serving) path
# ---------------------------------------------------------------------------

def _moe_apply_local(cfg, p: Params, x: jax.Array):
    topw, topi, me, ce = _route(cfg, p["router"], x)
    aux = _aux_loss(cfg, me, ce)

    def expert_fn(hidden):  # (B, E, C, d)
        def ff(h, gw, uw, dw):
            if any(isinstance(w, (QuantizedTensor, SparseQuantizedTensor))
                   for w in (gw, uw, dw)):
                # quantized serving experts: whole FFN as one op (fused
                # kernel on TPU, blocked-XLA twin elsewhere)
                return ops.ffn_w4a16(
                    h, gw, uw, dw, activation="swiglu",
                    impl="pallas" if cfg.use_kernels else "xla")
            a = jax.nn.silu(linear(h, gw, use_kernels=cfg.use_kernels)) * linear(
                h, uw, use_kernels=cfg.use_kernels)
            return linear(a, dw, use_kernels=cfg.use_kernels)

        return jax.vmap(jax.vmap(ff, in_axes=(0, 0, 0, 0)),
                        in_axes=(0, None, None, None))(
            hidden, p["gate"], p["up"], p["down"])

    out = _dispatch_compute(cfg, x, topi, topw, expert_fn)
    return out, aux


# ---------------------------------------------------------------------------
# shard_map (quantized serving) path
# ---------------------------------------------------------------------------

def _moe_apply_shard_map_quant(cfg, p: Params, x: jax.Array, mesh):
    """Serve-mode MoE with W4A16 experts under shard_map.

    The vmapped local path lets XLA's partitioner replicate the dispatch
    buffers across the model axis (1.5 TB/device temp on mixtral
    prefill_32k — §Perf it.8).  Here: experts TP-sharded over ``model`` on
    the hidden axis (packed nibbles + per-group scales shard together),
    dispatch runs redundantly per model shard (index math only), one psum
    after combine.  No FSDP gathers — serve weights replicate over data.

    Each expert's FFN dispatches through ``ops.ffn_w4a16`` (the fused
    Pallas kernel on TPU, the blocked-XLA twin elsewhere) — the dense
    dequantize-everything oracle is no longer in this hot loop.
    """
    da = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    M = mesh.shape["model"]
    gate, up, down = p["gate"], p["up"], p["down"]
    e = cfg.n_experts
    d = cfg.d_model
    f = cfg.d_ff
    gs_col = gate.group_size
    gs_row = down.group_size

    def local_fn(x_l, router, g_pk, g_sc, u_pk, u_sc, d_pk, d_sc):
        topw, topi, me, ce = _route(cfg, router, x_l)
        aux = _aux_loss(cfg, jax.lax.pmean(me, da), jax.lax.pmean(ce, da))

        f_loc = f // M

        def expert_fn(hidden):  # (B_l, E, C, d)
            def one(h, gp, gsc, upk, usc, dpk, dsc):
                gl = QuantizedTensor(gp, gsc, (d, f_loc), gs_col)
                ul = QuantizedTensor(upk, usc, (d, f_loc), gs_col)
                dl = QuantizedTensor(dpk, dsc, (f_loc, d), gs_row)
                return ops.ffn_w4a16(
                    h, gl, ul, dl, activation="swiglu",
                    impl="pallas" if cfg.use_kernels else "xla")

            return jax.vmap(one, in_axes=(1, 0, 0, 0, 0, 0, 0), out_axes=1)(
                hidden, g_pk, g_sc, u_pk, u_sc, d_pk, d_sc)

        out = _dispatch_compute(cfg, x_l, topi, topw, expert_fn)
        out = jax.lax.psum(out, "model")   # row-parallel down partials
        return out, aux

    col_pk = P(None, None, "model")       # (E, d/2, f)
    col_sc = P(None, None, "model")       # (E, d/gs, f)
    row_pk = P(None, "model", None)       # (E, f/2, d)
    row_sc = P(None, "model", None)       # (E, f/gs, d)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(da, None, None), P(), col_pk, col_sc, col_pk, col_sc,
                  row_pk, row_sc),
        out_specs=(P(da, None, None), P()),
    )
    return fn(x, p["router"], gate.packed, gate.scales, up.packed, up.scales,
              down.packed, down.scales)


# ---------------------------------------------------------------------------
# shard_map (training) path
# ---------------------------------------------------------------------------

def _moe_apply_shard_map(cfg, p: Params, x: jax.Array, mesh):
    da = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    wspec = da + ("model",)

    def local_fn(x_l, router, gate_l, up_l, down_l):
        # ZeRO-3 gather of the data-sharded expert weights (transpose =
        # reduce-scatter of their grads)
        gate = jax.lax.all_gather(gate_l, da, axis=2, tiled=True)
        up = jax.lax.all_gather(up_l, da, axis=2, tiled=True)
        down = jax.lax.all_gather(down_l, da, axis=1, tiled=True)

        topw, topi, me, ce = _route(cfg, router, x_l)
        # exact global aux: average the statistics, then take the product
        aux = _aux_loss(cfg, jax.lax.pmean(me, da), jax.lax.pmean(ce, da))

        def expert_fn(hidden):  # (B_l, E, C, d)
            # column-parallel gate/up (f/model local), row-parallel down.
            # NOTE: no psum here — the combine below is linear in the expert
            # outputs, so the Megatron row-parallel reduction moves AFTER
            # combine, shrinking its payload from E·C slots to S tokens
            # (capacity_factor × top_k / 1 ≈ 2.5× on mixtral; §Perf it.3)
            # compute-dtype operands AND outputs: f32 casts here get hoisted
            # before the FSDP all-gathers (2x gather bytes) and put the
            # d_hidden backward psum in f32 (2x wire) — §Perf it.4/5.  The
            # MXU still accumulates each dot in f32 internally.
            h = jnp.einsum("becd,edf->becf", hidden, gate)
            u = jnp.einsum("becd,edf->becf", hidden, up)
            a = jax.nn.silu(h.astype(jnp.float32)).astype(hidden.dtype) * u
            return jnp.einsum("becf,efd->becd", a, down)

        out = _dispatch_compute(cfg, x_l, topi, topw, expert_fn)
        out = jax.lax.psum(out, "model")                      # Megatron row sum
        return out, aux

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(da, None, None), P(), P(None, None, wspec),
                  P(None, None, wspec), P(None, wspec, None)),
        out_specs=(P(da, None, None), P()),
    )
    return fn(x, p["router"], p["gate"], p["up"], p["down"])


def moe_apply(cfg, p: Params, x: jax.Array):
    """x (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    mesh = active_mesh()
    if mesh is None or "model" not in mesh.axis_names or (
            x.shape[0] % _data_size(mesh)):
        return _moe_apply_local(cfg, p, x)
    M = mesh.shape["model"]
    if isinstance(p["gate"], (jax.Array, jax.ShapeDtypeStruct)):
        if cfg.d_ff % (_data_size(mesh) * M) == 0:
            return _moe_apply_shard_map(cfg, p, x, mesh)
    elif isinstance(p["gate"], QuantizedTensor):
        f, gs_row = cfg.d_ff, p["down"].group_size
        if f % M == 0 and (f // 2) % M == 0 and (f // gs_row) % M == 0:
            return _moe_apply_shard_map_quant(cfg, p, x, mesh)
    return _moe_apply_local(cfg, p, x)


def _data_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n
