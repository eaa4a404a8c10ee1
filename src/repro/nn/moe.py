"""Mixture-of-Experts with expert parallelism.

The router *is* a K-winner circuit (paper C3): top-k expert selection over the
router logits is exactly the KWN selection the macro performs over its 128
columns, and we expose the same knobs — including an optional SNL-style
probabilistic rescue of near-threshold experts (beyond-paper ablation).

Two execution paths:

* ``moe_a2a``   — production EP: shard_map over ("data","model") with tokens
  sharded over data and *sliced* over model, capacity-based dispatch, two
  all_to_alls over the model axis, batched per-expert GEMMs.  Used for
  train/prefill shapes (many tokens per device).
* ``moe_dense_ep`` — small-token fallback (decode): every model shard runs its
  local experts on all local tokens, combines with the routing mask, psum over
  model.  Redundant by E_local/k flops but collective-light; right for T_loc
  of a few tokens.

Both are numerically equal to the reference dense formulation (``moe_ref``)
up to capacity drops (a2a path with cf < inf).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.nn import layers
from repro.nn.module import ParamSpec


def moe_specs(d_model: int, d_ff: int, n_experts: int, dtype=jnp.float32,
              router_dtype=jnp.float32) -> dict:
    return {
        "router": ParamSpec((d_model, n_experts), ("embed", None), router_dtype,
                            scale=0.02),
        "w_in": ParamSpec((n_experts, d_model, d_ff),
                          ("experts", "expert_in", "expert_ffn"), dtype),
        "w_gate": ParamSpec((n_experts, d_model, d_ff),
                            ("experts", "expert_in", "expert_ffn"), dtype),
        "w_out": ParamSpec((n_experts, d_ff, d_model),
                           ("experts", "expert_ffn", "expert_in"), dtype),
    }


def router_topk(logits: jax.Array, k: int, *, snl_rescue: float = 0.0,
                rng: jax.Array | None = None):
    """KWN selection over expert logits.

    snl_rescue > 0 enables the SNL analogue: experts whose softmax prob lands
    within ``snl_rescue`` of the k-th winner get a probabilistic chance to
    displace it (PRBS noise -> here a gumbel kick on the boundary band).
    """
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if snl_rescue > 0.0 and rng is not None:
        kth = jnp.sort(probs, axis=-1)[..., -k][..., None]
        band = (probs > kth - snl_rescue) & (probs < kth + snl_rescue)
        kick = snl_rescue * jax.random.gumbel(rng, probs.shape) * 0.5
        probs = jnp.where(band, probs + kick, probs)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return gate, idx, probs


def _expert_ffn(w_in, w_gate, w_out, x, activation):
    act = layers.ACTIVATIONS[activation]
    h = act(jnp.einsum("ecd,edf->ecf", x, w_in.astype(x.dtype)))
    g = jnp.einsum("ecd,edf->ecf", x, w_gate.astype(x.dtype))
    return jnp.einsum("ecf,efd->ecd", h * g, w_out.astype(x.dtype))


def aux_load_balance_loss(probs: jax.Array, idx: jax.Array, n_experts: int,
                          k: int) -> jax.Array:
    """Switch-style load-balancing auxiliary loss."""
    me = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    assign = jax.nn.one_hot(idx, n_experts).sum(-2)
    ce = jnp.mean(assign, axis=tuple(range(assign.ndim - 1))) / k
    return n_experts * jnp.sum(me * ce)


# ---------------------------------------------------------------------------
# Reference (single-device semantics; also the smoke-test path)
# ---------------------------------------------------------------------------

def moe_ref(p: dict, x: jax.Array, *, k: int, activation: str = "silu",
            snl_rescue: float = 0.0, rng=None):
    """Dense-combine reference: computes every expert on every token.
    x: (..., D).  Only for small configs (tests / smoke)."""
    gate, idx, probs = router_topk(x @ p["router"].astype(x.dtype), k,
                                   snl_rescue=snl_rescue, rng=rng)
    n_experts = p["w_in"].shape[0]
    lead = x.shape[:-1]
    xt = x.reshape(1, -1, x.shape[-1])                      # (1, T, D)
    outs = _expert_ffn(p["w_in"], p["w_gate"], p["w_out"],
                       jnp.broadcast_to(xt, (n_experts,) + xt.shape[1:]),
                       activation)                          # (E, T, D)
    combine = jax.nn.one_hot(idx, n_experts, dtype=x.dtype) * gate[..., None].astype(x.dtype)
    combine = combine.sum(-2).reshape(-1, n_experts)        # (T, E)
    y = jnp.einsum("te,etd->td", combine, outs)
    aux = aux_load_balance_loss(probs, idx, n_experts, k)
    return y.reshape(*lead, x.shape[-1]), aux


# ---------------------------------------------------------------------------
# Expert-parallel paths (inside shard_map)
# ---------------------------------------------------------------------------

def _dispatch_onehot(idx, gate, n_experts, capacity, dtype):
    """Capacity-based dispatch/combine tensors from top-k routing.

    idx/gate: (T, k).  Returns dispatch (T, E, C) {0,1}, combine (T, E, C)."""
    t, k = idx.shape
    e_oh = jax.nn.one_hot(idx, n_experts, dtype=jnp.int32)          # (T,k,E)
    flat = e_oh.reshape(t * k, n_experts)
    # position of each assignment within its expert queue (token-major order)
    pos = jnp.cumsum(flat, axis=0) - flat                            # (T*k,E)
    pos = (pos * flat).sum(-1).reshape(t, k)                         # (T,k)
    keep = pos < capacity
    pos_oh = jax.nn.one_hot(pos, capacity, dtype=dtype) * keep[..., None].astype(dtype)
    disp = jnp.einsum("tke,tkc->tec", e_oh.astype(dtype), pos_oh)
    comb = jnp.einsum("tke,tkc->tec",
                      (e_oh.astype(dtype) * gate[..., None].astype(dtype)),
                      pos_oh)
    return disp, comb


def moe_a2a(p: dict, x: jax.Array, *, k: int, mesh: Mesh,
            activation: str = "silu", capacity_factor: float = 1.25,
            token_axes=("pod", "data"), expert_axis: str = "model",
            seq_sharded: bool = False, snl_rescue: float = 0.0, rng=None):
    """Expert-parallel MoE via all_to_all. x: (B, S, D) -> (B, S, D), aux.

    Inside shard_map: tokens are sharded over ``token_axes`` and additionally
    over ``expert_axis`` (via the caller's sequence sharding when
    ``seq_sharded``, else by an explicit axis_index slice), sent to expert
    owners with all_to_all, computed as batched per-expert GEMMs, returned.
    """
    b, s, d = x.shape
    n_experts = p["w_in"].shape[0]
    taxes = tuple(a for a in token_axes if a in mesh.shape)
    tp = mesh.shape[expert_axis]
    if seq_sharded and s % tp != 0:
        seq_sharded = False   # fall back to the slice path

    def local_fn(router, w_in, w_gate, w_out, xl):
        bl, sl, dl = xl.shape
        t_loc = bl * sl
        xt = xl.reshape(t_loc, dl)
        if seq_sharded:
            xs = xt                                   # already sliced by spec
            t_slice = t_loc
        else:
            my = jax.lax.axis_index(expert_axis)
            assert t_loc % tp == 0, (t_loc, tp)
            t_slice = t_loc // tp
            xs = jax.lax.dynamic_slice_in_dim(xt, my * t_slice, t_slice, 0)

        gate, idx, probs = router_topk(xs @ router.astype(xs.dtype), k,
                                       snl_rescue=snl_rescue, rng=rng)
        capacity = max(1, int(math.ceil(t_slice * k / n_experts
                                        * capacity_factor)))
        disp, comb = _dispatch_onehot(idx, gate, n_experts, capacity, xs.dtype)
        x_send = jnp.einsum("tec,td->ecd", disp, xs)          # (E, C, D)
        # exchange: every device sends each expert-owner its (E_loc, C, D)
        x_recv = jax.lax.all_to_all(x_send, expert_axis, split_axis=0,
                                    concat_axis=1, tiled=True)  # (E_loc, tp*C, D)
        y_loc = _expert_ffn(w_in, w_gate, w_out, x_recv, activation)
        y_send = jax.lax.all_to_all(y_loc, expert_axis, split_axis=1,
                                    concat_axis=0, tiled=True)  # (E, C, D)
        ys = jnp.einsum("ecd,tec->td", y_send, comb)            # (T_slice, D)
        if not seq_sharded:
            # reassemble the full local token set across the expert axis
            ys = jax.lax.all_gather(ys, expert_axis, axis=0, tiled=True)
        aux = aux_load_balance_loss(probs, idx, n_experts, k)
        aux = jax.lax.pmean(aux, expert_axis)
        for ax in taxes:
            aux = jax.lax.pmean(aux, ax)
        return ys.reshape(bl, sl, dl), aux

    tspec = P(taxes if len(taxes) > 1 else (taxes[0] if taxes else None))
    seq_spec = expert_axis if seq_sharded else None
    in_specs = (P(), P(expert_axis), P(expert_axis), P(expert_axis),
                P(*tspec, seq_spec, None))
    out_specs = (P(*tspec, seq_spec, None), P())
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(p["router"], p["w_in"], p["w_gate"], p["w_out"], x)


def _wire_quantize(x: jax.Array):
    """Per-(expert, slot) int8 quantization for dispatch payloads (§Perf:
    collective compression — the paper's NLQ idea applied to the wire)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                        1e-8).astype(jnp.float32) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale


def _wire_dequantize(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def moe_2d(p: dict, x: jax.Array, *, k: int, mesh: Mesh,
           activation: str = "silu", capacity_factor: float = 1.25,
           expert_axes=("pod", "data"), tp_axis: str = "model",
           wire_dtype: str = "bfloat16",
           snl_rescue: float = 0.0, rng=None):
    """2D expert + tensor parallelism (the 1T-scale path).

    Expert weights are sharded over BOTH grid axes: experts over
    ``expert_axes`` (the DP rows) and the expert FFN dim over ``tp_axis`` —
    so a 1T-param MoE's weights/grads/moments divide by all 256/512 chips.

    Dataflow per layer (tokens arrive sharded batch x seq = rows x model):
      1. local routing on each device's token slice (distinct per device);
      2. all_to_all over the expert rows -> tokens reach their expert's row;
      3. all_gather over ``tp_axis`` (every model shard needs the full token
         set for TP), batched expert GEMMs on the local F-slice;
      4. psum_scatter over ``tp_axis`` -> finished tokens back to their
         sender's model shard;
      5. reverse all_to_all; combine with gates.
    """
    b, s, d = x.shape
    n_experts = p["w_in"].shape[0]
    eaxes = tuple(a for a in expert_axes if a in mesh.shape)
    tp = mesh.shape[tp_axis]
    seq_ok = s % tp == 0

    def local_fn(router, w_in, w_gate, w_out, xl):
        bl, sl, dl = xl.shape
        xs = xl.reshape(bl * sl, dl)
        t_slice = xs.shape[0]
        gate, idx, probs = router_topk(xs @ router.astype(xs.dtype), k,
                                       snl_rescue=snl_rescue, rng=rng)
        capacity = max(1, int(math.ceil(t_slice * k / n_experts
                                        * capacity_factor)))
        disp, comb = _dispatch_onehot(idx, gate, n_experts, capacity, xs.dtype)
        x_send = jnp.einsum("tec,td->ecd", disp, xs)            # (E, C, D)
        if wire_dtype == "int8":
            # quantize once; stays int8 through the a2a AND the TP gather
            xq, xscale = _wire_quantize(x_send)
            xq = jax.lax.all_to_all(xq, eaxes, split_axis=0,
                                    concat_axis=1, tiled=True)
            xscale = jax.lax.all_to_all(xscale, eaxes, split_axis=0,
                                        concat_axis=1, tiled=True)
            xq = jax.lax.all_gather(xq, tp_axis, axis=1, tiled=True)
            xscale = jax.lax.all_gather(xscale, tp_axis, axis=1, tiled=True)
            x_full = _wire_dequantize(xq, xscale, x_send.dtype)
        else:
            x_recv = jax.lax.all_to_all(x_send, eaxes, split_axis=0,
                                        concat_axis=1, tiled=True)  # (E_loc, R*C, D)
            # TP over the expert FFN dim: gather tokens for the F-slice GEMMs.
            x_full = jax.lax.all_gather(x_recv, tp_axis, axis=1, tiled=True)
        # name the post-communication tensor so a remat policy can pin it
        # (save_only_these_names -> the x-side a2a+gather is not re-run in
        # the backward recompute; §Perf "save_moe_recv" iteration)
        from jax.ad_checkpoint import checkpoint_name
        x_full = checkpoint_name(x_full, "moe_xfull")
        ACT = layers.ACTIVATIONS[activation]
        h = ACT(jnp.einsum("ecd,edf->ecf", x_full, w_in.astype(x_full.dtype)))
        g = jnp.einsum("ecd,edf->ecf", x_full, w_gate.astype(x_full.dtype))
        y_part = jnp.einsum("ecf,efd->ecd", h * g, w_out.astype(x_full.dtype))
        y_loc = jax.lax.psum_scatter(y_part, tp_axis, scatter_dimension=1,
                                     tiled=True)                # (E_loc, R*C, D)
        if wire_dtype == "int8":
            yq, yscale = _wire_quantize(y_loc)
            yq = jax.lax.all_to_all(yq, eaxes, split_axis=1,
                                    concat_axis=0, tiled=True)
            yscale = jax.lax.all_to_all(yscale, eaxes, split_axis=1,
                                        concat_axis=0, tiled=True)
            y_send = _wire_dequantize(yq, yscale, y_loc.dtype)
        else:
            y_send = jax.lax.all_to_all(y_loc, eaxes, split_axis=1,
                                        concat_axis=0, tiled=True)  # (E, C, D)
        ys = jnp.einsum("ecd,tec->td", y_send, comb)
        aux = aux_load_balance_loss(probs, idx, n_experts, k)
        aux = jax.lax.pmean(aux, eaxes + (tp_axis,))
        return ys.reshape(bl, sl, dl), aux

    row_spec = eaxes if len(eaxes) > 1 else (eaxes[0] if eaxes else None)
    seq_spec = tp_axis if seq_ok else None
    in_specs = (P(),
                P(row_spec, None, tp_axis),     # w_in  (E, D, F)
                P(row_spec, None, tp_axis),     # w_gate
                P(row_spec, tp_axis, None),     # w_out (E, F, D)
                P(row_spec, seq_spec, None))
    out_specs = (P(row_spec, seq_spec, None), P())
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(p["router"], p["w_in"], p["w_gate"], p["w_out"], x)


def moe_dense_ep_2d(p: dict, x: jax.Array, *, k: int, mesh: Mesh,
                    activation: str = "silu", expert_axes=("pod", "data"),
                    tp_axis: str = "model", snl_rescue: float = 0.0, rng=None):
    """Decode-shape path for 2D-sharded experts: all-gather the (tiny) token
    batch over the expert rows, run every local expert's F-slice on all
    tokens, psum over model (TP) then over rows (expert combine), slice back.
    """
    b, s, d = x.shape
    n_experts = p["w_in"].shape[0]
    eaxes = tuple(a for a in expert_axes if a in mesh.shape)
    n_rows = 1
    for a in eaxes:
        n_rows *= mesh.shape[a]
    e_loc = n_experts // n_rows

    def local_fn(router, w_in, w_gate, w_out, xl):
        bl, sl, dl = xl.shape
        xt = xl.reshape(-1, dl)
        x_all = jax.lax.all_gather(xt, eaxes, axis=0, tiled=True)  # (T, D)
        gate, idx, probs = router_topk(x_all @ router.astype(x_all.dtype), k,
                                       snl_rescue=snl_rescue, rng=rng)
        row = jax.lax.axis_index(eaxes[0]) if len(eaxes) == 1 else (
            jax.lax.axis_index(eaxes[0]) * mesh.shape[eaxes[1]]
            + jax.lax.axis_index(eaxes[1]))
        ACT = layers.ACTIVATIONS[activation]
        xb = jnp.broadcast_to(x_all[None], (e_loc,) + x_all.shape)
        h = ACT(jnp.einsum("ecd,edf->ecf", xb, w_in.astype(xb.dtype)))
        g = jnp.einsum("ecd,edf->ecf", xb, w_gate.astype(xb.dtype))
        y = jnp.einsum("ecf,efd->ecd", h * g, w_out.astype(xb.dtype))
        combine = (jax.nn.one_hot(idx, n_experts, dtype=xt.dtype)
                   * gate[..., None].astype(xt.dtype)).sum(-2)   # (T, E)
        local_comb = jax.lax.dynamic_slice_in_dim(combine, row * e_loc,
                                                  e_loc, axis=1)
        y_tok = jnp.einsum("te,etd->td", local_comb, y)
        y_tok = jax.lax.psum(y_tok, (tp_axis,) + eaxes)
        # slice my batch rows back out of the gathered order
        t_loc = xt.shape[0]
        y_mine = jax.lax.dynamic_slice_in_dim(y_tok, row * t_loc, t_loc, 0)
        aux = aux_load_balance_loss(probs, idx, n_experts, k)
        aux = jax.lax.pmean(aux, eaxes + (tp_axis,))
        return y_mine.reshape(bl, sl, dl), aux

    row_spec = eaxes if len(eaxes) > 1 else (eaxes[0] if eaxes else None)
    in_specs = (P(), P(row_spec, None, tp_axis), P(row_spec, None, tp_axis),
                P(row_spec, tp_axis, None), P(row_spec, None, None))
    out_specs = (P(row_spec, None, None), P())
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(p["router"], p["w_in"], p["w_gate"], p["w_out"], x)


def moe_dense_ep(p: dict, x: jax.Array, *, k: int, mesh: Mesh,
                 activation: str = "silu", token_axes=("pod", "data"),
                 expert_axis: str = "model", snl_rescue: float = 0.0,
                 rng=None):
    """Decode-shape EP: all local experts on all local tokens, psum combine."""
    b, s, d = x.shape
    n_experts = p["w_in"].shape[0]
    tp = mesh.shape[expert_axis]
    e_loc = n_experts // tp
    taxes = tuple(a for a in token_axes if a in mesh.shape)

    def local_fn(router, w_in, w_gate, w_out, xl):
        bl, sl, dl = xl.shape
        xt = xl.reshape(-1, dl)                               # (T_loc, D)
        gate, idx, probs = router_topk(xt @ router.astype(xt.dtype), k,
                                       snl_rescue=snl_rescue, rng=rng)
        my = jax.lax.axis_index(expert_axis)
        outs = _expert_ffn(w_in, w_gate, w_out,
                           jnp.broadcast_to(xt[None], (e_loc,) + xt.shape),
                           activation)                        # (E_loc, T, D)
        combine = (jax.nn.one_hot(idx, n_experts, dtype=xt.dtype)
                   * gate[..., None].astype(xt.dtype)).sum(-2)  # (T, E)
        local_combine = jax.lax.dynamic_slice_in_dim(
            combine, my * e_loc, e_loc, axis=1)               # (T, E_loc)
        y = jnp.einsum("te,etd->td", local_combine, outs)
        y = jax.lax.psum(y, expert_axis)
        aux = aux_load_balance_loss(probs, idx, n_experts, k)
        for ax in taxes:
            aux = jax.lax.pmean(aux, ax)
        return y.reshape(bl, sl, dl), aux

    tspec = P(taxes if len(taxes) > 1 else (taxes[0] if taxes else None))
    in_specs = (P(), P(expert_axis), P(expert_axis), P(expert_axis),
                P(*tspec, None, None))
    out_specs = (P(*tspec, None, None), P())
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(p["router"], p["w_in"], p["w_gate"], p["w_out"], x)
