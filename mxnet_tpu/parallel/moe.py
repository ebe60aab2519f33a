"""Expert parallelism: a Switch-style Mixture-of-Experts FFN sharded over
an ``ep`` mesh axis with real ``lax.all_to_all`` token exchange.

Reference capability: absent upstream as a named subsystem (MXNet-era
MoE lived in user code); TPU-natively this is the canonical ``ep`` axis
of the dp/tp/pp/sp/ep sharding family.  Design (the GShard/Switch
recipe):

* tokens are sharded over ``ep`` (each device owns S = N/ndev tokens);
* a replicated router picks top-1 expert per token; each (source shard,
  expert) pair gets a fixed capacity C — static shapes, overflow tokens
  pass through the residual untouched (standard Switch behaviour);
* dispatch is a one-hot (S, E, C) tensor; the send buffer
  (ndev, E_loc, C, H) crosses the mesh with ``lax.all_to_all``, experts
  run their FFN on (E_loc, ndev*C, H), and a second all_to_all returns
  expert outputs to the token owners, combined with the router gate;
* everything differentiates: all_to_all is linear, the router gate
  carries the straight-through softmax weight.

``moe_ffn_ref`` is the single-device oracle with identical routing
semantics (same per-shard capacity drops) used by the tests.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["moe_ffn_init", "moe_ffn_apply", "moe_ffn_ref"]


def moe_ffn_init(rng, hidden, ffn, n_experts, dtype=jnp.float32):
    """Parameter pytree: router (H, E), w1 (E, H, F), w2 (E, F, H)."""
    import numpy as onp
    rs = onp.random.RandomState(rng)
    s1 = 1.0 / math.sqrt(hidden)
    s2 = 1.0 / math.sqrt(ffn)
    return {
        "router": jnp.asarray(rs.randn(hidden, n_experts) * s1, dtype),
        "w1": jnp.asarray(rs.randn(n_experts, hidden, ffn) * s1, dtype),
        "w2": jnp.asarray(rs.randn(n_experts, ffn, hidden) * s2, dtype),
    }


def _route(x, router_w, n_experts, capacity):
    """Shared routing math: (S, H) tokens → dispatch (S, E, C) one-hot,
    combine (S, E, C) gate-weighted, both zero beyond capacity."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)            # (S, E)
    expert = jnp.argmax(probs, axis=-1)                # (S,)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.float32)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0    # position in expert
    keep = (pos >= 0) & (pos < capacity)
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, 0).astype(jnp.int32),
                            capacity, dtype=jnp.float32)
    dispatch = (onehot[:, :, None] * pos_oh
                * keep.astype(jnp.float32)[:, :, None])
    combine = dispatch * gate[:, None, None]
    return dispatch, combine


def _expert_ffn(w1, w2, x):
    """(E?, C?, H) per-expert GELU MLP via batched einsum."""
    h = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", x, w1))
    return jnp.einsum("ecf,efh->ech", h, w2)


def moe_ffn_apply(params, x, mesh: Mesh, axis: str = "ep",
                  capacity_factor: float = 1.25):
    """MoE FFN over token-sharded input x (N, H) → (N, H).

    ``params['w1']/['w2']`` leading (expert) dim shards over ``axis``;
    the router is replicated.  N must divide by the axis size.
    """
    ndev = mesh.shape[axis]
    E = params["w1"].shape[0]
    if E % ndev:
        raise ValueError("n_experts %d must divide over %r size %d"
                         % (E, axis, ndev))
    N, H = x.shape
    if N % ndev:
        raise ValueError("token count %d must shard over %r size %d"
                         % (N, axis, ndev))
    S = N // ndev
    E_loc = E // ndev
    capacity = max(1, int(capacity_factor * S / E))

    def per_shard(params, xs):
        xl = xs                                     # (S, H) local tokens
        dispatch, combine = _route(xl, params["router"], E, capacity)
        # send buffer: tokens grouped by destination device
        send = jnp.einsum("sec,sh->ech", dispatch,
                          xl.astype(jnp.float32))   # (E, C, H)
        send = send.reshape(ndev, E_loc, capacity, H)
        recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                              tiled=False)          # (ndev, E_loc, C, H)
        # my experts' inputs from every source shard; params["w1"]/["w2"]
        # arrive as the LOCAL (E_loc, ...) expert slice (in_specs P(axis))
        ein = jnp.moveaxis(recv, 0, 1).reshape(E_loc, ndev * capacity, H)
        eout = _expert_ffn(params["w1"].astype(jnp.float32),
                           params["w2"].astype(jnp.float32),
                           ein)                     # (E_loc, ndev*C, H)
        back = jnp.moveaxis(eout.reshape(E_loc, ndev, capacity, H), 1, 0)
        got = lax.all_to_all(back, axis, split_axis=0, concat_axis=0,
                             tiled=False)           # (ndev, E_loc, C, H)
        got = got.reshape(E, capacity, H)
        out = jnp.einsum("sec,ech->sh", combine, got)
        return out.astype(x.dtype)

    in_specs = ({"router": P(), "w1": P(axis), "w2": P(axis)}, P(axis))
    fn = jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                       out_specs=P(axis), check_vma=False)
    return fn(params, x)


def moe_ffn_ref(params, x, n_shards, capacity_factor: float = 1.25):
    """Single-device oracle with the sharded routing semantics: tokens
    are processed in ``n_shards`` groups, each with its own per-expert
    capacity, exactly like the ``ep``-sharded kernel."""
    N, H = x.shape
    E = params["w1"].shape[0]
    if N % n_shards:
        raise ValueError("token count %d must divide into %d shards"
                         % (N, n_shards))
    S = N // n_shards
    capacity = max(1, int(capacity_factor * S / E))
    outs = []
    for s in range(n_shards):
        xl = x[s * S:(s + 1) * S]
        dispatch, combine = _route(xl, params["router"], E, capacity)
        ein = jnp.einsum("sec,sh->ech", dispatch, xl.astype(jnp.float32))
        eout = _expert_ffn(params["w1"].astype(jnp.float32),
                           params["w2"].astype(jnp.float32), ein)
        outs.append(jnp.einsum("sec,ech->sh", combine,
                               eout).astype(x.dtype))
    return jnp.concatenate(outs, axis=0)
