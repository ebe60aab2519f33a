"""Ling-3.0-flash, one chip's share of a 64-chip expert-parallel deployment:
the builder through the system's normal path (``gluon.model_zoo.ling`` ->
``DataParallelStep`` with ``Adam(multi_precision=True)`` and the blocked
cross-entropy over the untied head), the plain reference, and the FLOP
counts.

The reference is float32 ``jax.numpy`` at ``highest`` matmul precision,
written from the equations in ``config.json``'s ``assumed`` and sharing no
code with the system: the delta rule runs TOKEN BY TOKEN (``lax.scan`` over
t on a (heads, 128, 128) state; the system computes it in chunks), latent
attention forms the full scores of a block of query rows against all keys
in the published [nope | rope] order with the interleaved rotary written
out (the system reorders the dimensions and pads v for its kernels), the
routing takes its own groups, the experts are a dense loop, no kernels, no
sort.  It is given the same share as the system: the experts and the rows
of the vocabulary that ``deployment`` says are held here, and the shared
expert once.

A top-8 choice from 4 of 8 groups is discontinuous twice: where a
token's 8th and 9th ``s + b`` (after the group mask) tie within what
bfloat16 resolves, or its 4th and 5th groups' scores do, the system and
the float32 reference pick different sets and the two answers differ by a
whole expert's output at that token.  So ``compare`` has three parts, as
the other expert cells': the logits are compared with the reference
FOLLOWING the system's chosen sets (the gates stay the reference's own
scores of them); at least ``ROUTING_AGREEMENT`` of every layer's routes go
where the reference sends them; and of the tokens the reference routes
CLEARLY (its 8th and 9th ``s + b`` after the group mask, and its 4th and
5th group scores, each further apart than ``CLEAR_GAP`` of the layer's
standard deviation of s) at most ``CLEAR_DISAGREEMENT`` have another
set.  Three controls have to fail (PERF.md section 6 has every
reading): the reference in float8; the reference with the delta
rule's erase term dropped (``no_erase``: ``S_t = Diag(alpha_t) S_(t-1) +
beta_t k_t v_t^T``); the reference with one decay a head (``head_decay``:
alpha averaged over its 128 channels).  Half the compared positions lie
past token ``check_positions_past``.
"""
import json

import numpy as onp

QUERY_ROWS = 512      # the reference's attention: query rows a block
# The limits of ``compare``, each between two readings on the chip (PERF.md
# section 6): bfloat16 over the seeds tried, and the reference with
# every activation a matrix product reads or writes rounded to float8
# (e4m3, a scale a row).
ROUTING_AGREEMENT = 0.96     # of a layer's routes: 0.9728-0.9880 / 0.796-0.894
CLEAR_GAP = 0.1              # of the standard deviation of a layer's s
CLEAR_DISAGREEMENT = 0.005   # of the clearly routed tokens: 0 / 0.074-0.585

# The block kinds of the step's device time (``reduce_trace.kind_of``): a
# pattern is searched in each scope of an operation's ``op_name``, the
# outermost match decides.  The delta attention layers' pre-norm, input and
# output projections, and the scopes ``kda.*`` round the rule are
# ``linear_attention``; the rule's own scopes ``delta.*`` are
# ``delta_scan`` (the layer's block ``layerN_kda`` holds both and is matched
# by neither, so it cannot swallow the scan); in the backward a scope is
# named inside autodiff's own, ``transpose(jvp(delta.intra))``.  The latent
# layer with its pre-norm is ``attention``; a sparse layer's feed-forward
# norm goes with the router, which reads it first.  Layer 0 is the one
# dense layer.
BLOCKS = {
    "linear_attention": [r"_layer\d+_kda_(norm|proj|out)$",
                         r"^(\w+\()*kda\."],
    "delta_scan": [r"^(\w+\()*delta\."],
    "attention": [r"_layer\d+_mla(_norm)?$"],
    "experts": [r"_layer\d+_(experts|router)$", r"_layer[1-9]\d*_ffn_norm$",
                r"^ragged-dot-"],
    "ffn": [r"_layer0_ffn(_norm)?$", r"_layer\d+_shared$"],
    "head": [r"_embed$", r"_final_norm$",
             r"^tiedsoftmaxcrossentropyloss\d+$"],
}


def _kept(sizes):
    """The published indices of the layers kept."""
    return list(sizes["deployment"]["layers_kept"])[
        :sizes["num_hidden_layers"]]


def _kinds(sizes, index):
    """(mixer, feed-forward) of published layer ``index``: ``mla`` where
    ``(index + 1) % layer_group_size == 0``, else ``kda``; ``dense`` below
    ``first_k_dense_replace``, else ``sparse``."""
    mixer = "mla" if (index + 1) % sizes["layer_group_size"] == 0 else "kda"
    return mixer, "dense" if index < sizes["first_k_dense_replace"] \
        else "sparse"


def _sparse_layers(sizes):
    return [i for i in _kept(sizes) if _kinds(sizes, i)[1] == "sparse"]


def _net(sizes):
    from mxnet_tpu.gluon.model_zoo import ling

    heads = sizes["num_attention_heads"]
    if sizes["num_kv_heads_for_linear_attn"] not in (0, heads):
        raise ValueError("delta attention with %d key-value heads"
                         % sizes["num_kv_heads_for_linear_attn"])
    return ling(
        vocab_size=sizes["vocab_size"], units=sizes["hidden_size"],
        num_layers=sizes["published"]["num_hidden_layers"],
        layers=_kept(sizes), layer_group_size=sizes["layer_group_size"],
        first_k_dense_replace=sizes["first_k_dense_replace"],
        num_heads=heads, head_dim=sizes["head_dim"],
        conv_kernel=sizes["short_conv_kernel_size"],
        lower_bound=float(sizes["kda_lower_bound"]),
        chunk_size=sizes["delta_rule"]["chunk"],
        kv_rank=sizes["kv_lora_rank"], nope_dim=sizes["qk_nope_head_dim"],
        rope_dim=sizes["qk_rope_head_dim"], v_dim=sizes["v_head_dim"],
        mla_rope=dict(theta=float(sizes["rope_theta"]),
                      interleave=bool(sizes["rope_interleave"])),
        dense_hidden=sizes["intermediate_size"],
        num_experts=sizes["published"]["num_experts"],
        experts_per_token=sizes["num_experts_per_tok"],
        expert_groups=sizes["n_group"], topk_groups=sizes["topk_group"],
        expert_hidden=sizes["moe_intermediate_size"],
        shared_hidden=sizes["moe_shared_expert_intermediate_size"],
        routed_scale=sizes["routed_scaling_factor"],
        experts_held=tuple(sizes["deployment"]["experts_held"]),
        bias_update_rate=sizes["train"]["bias_update_rate"],
        epsilon=sizes["rms_norm_eps"])


def draw_tokens(sizes, rs, batch):
    """Token ids with text-like frequencies: Zipf over the ids of the
    slice, id = rank; labels are the next token, -1 (predicts nothing)
    in the last column."""
    vocab, seq = sizes["vocab_size"], sizes["seq_len"]
    weight = (onp.arange(vocab) + 1.0) ** -sizes["train"][
        "token_zipf_exponent"]
    tokens = rs.choice(vocab, size=(batch, seq), p=weight / weight.sum())
    labels = onp.concatenate(
        [tokens[:, 1:], -onp.ones((batch, 1), tokens.dtype)], axis=1)
    return tokens, labels


def draw_positions(sizes, rs, batch):
    """The places whose logits are compared: ``check_positions_per_row`` a
    row, half before token ``check_positions_past`` and half past it."""
    train, seq = sizes["train"], sizes["seq_len"]
    past, half = train["check_positions_past"], \
        train["check_positions_per_row"] // 2
    return onp.sort(onp.stack([onp.concatenate(
        [rs.choice(past, half, replace=False),
         past + rs.choice(seq - past, half, replace=False)])
        for _ in range(batch)]), 1)


def host_params(net):
    """The net's parameters as float32 numpy arrays, by the zoo's names
    without the model's prefix."""
    return {name[len(net.prefix):]: onp.asarray(
        p.data().asnumpy()).astype("float32")
        for name, p in net.collect_params().items()}


def routing_agreement(scores, picked, chosen, k, groups, kept):
    """How the chosen sets (layers, B, S, k) stand to the reference's own
    — from ``kept`` of ``groups`` groups of experts, the ``k`` largest of
    ``picked`` (layers, B, S, experts: ``s + b``), a group's score the sum
    of its two largest — a layer at a time: the share of routes the
    reference has too, the share of tokens it routes clearly (its 8th and
    9th ``s + b`` in the kept groups, and its ``kept``-th and next group
    scores, each further apart than ``CLEAR_GAP`` of the layer's standard
    deviation of ``scores``), and the share of THOSE whose set differs."""
    lead = picked.shape[:-1]
    by_group = picked.reshape(lead + (groups, -1))
    group_score = -onp.sort(-by_group, axis=-1)[..., :2].sum(-1)
    group_rank = onp.argsort(-group_score, axis=-1, kind="stable")
    keep = (group_rank[..., :kept, None]
            == onp.arange(groups)).any(-2)
    masked = onp.where(keep[..., None], by_group, -onp.inf).reshape(
        picked.shape)
    ranked = onp.argsort(-masked, axis=-1, kind="stable")
    own = onp.sort(ranked[..., :k], axis=-1)
    ordered = onp.take_along_axis(masked, ranked[..., :k + 1], axis=-1)
    ordered_groups = onp.take_along_axis(group_score,
                                         group_rank[..., kept - 1:kept + 1],
                                         axis=-1)
    limit = CLEAR_GAP * scores.std(axis=(1, 2, 3))[:, None, None]
    clear = (ordered[..., k - 1] - ordered[..., k] > limit) \
        & (ordered_groups[..., 0] - ordered_groups[..., 1] > limit)
    shared = (onp.sort(chosen, axis=-1)[..., :, None]
              == own[..., None, :]).any(-1).sum(-1)
    agreement = shared.mean(axis=(1, 2)) / k
    differ = shared < k
    return agreement, clear.mean(axis=(1, 2)), \
        (differ & clear).sum(axis=(1, 2)) / onp.maximum(
            clear.sum(axis=(1, 2)), 1)


def float8_rounded(x):
    """The lower-precision control's rounding: float8 (e4m3), a scale a
    row."""
    import jax.numpy as jnp

    scale = jnp.maximum(jnp.abs(x).max(-1, keepdims=True), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def compare(logits, chosen, params, tokens, positions, sizes, float8=False,
            no_erase=False, head_decay=False):
    """What ``correct.logits_agree`` is handed: the logits (B, P, V) of a
    forward whose sparse layers routed the tokens to ``chosen`` (sparse
    layers, B, S, k), and the reference's at the same ``positions`` with
    its experts run on those sets.  Where the routing itself fails one of
    its two limits (the module's docstring) the logits handed on are NaN:
    no verdict.  ``float8``, ``no_erase`` and ``head_decay`` are the three
    controls, each of which has to come out as not correct."""
    hidden, scores, picked = reference_hidden(
        params, tokens, sizes, follow=chosen,
        rounded=float8_rounded if float8 else None, no_erase=no_erase,
        head_decay=head_decay)
    agreement, clear, clear_differ = routing_agreement(
        scores, picked, chosen, sizes["num_experts_per_tok"],
        sizes["n_group"], sizes["topk_group"])
    routed_alike = bool(agreement.min() >= ROUTING_AGREEMENT
                        and clear_differ.max() <= CLEAR_DISAGREEMENT)
    got = onp.asarray(logits, "float32")
    want = reference_logits(params, hidden, positions)
    print("[check] %s" % json.dumps(
        {"routing_agreement_by_layer": agreement.tolist(),
         "floor": ROUTING_AGREEMENT,
         "clear_share_by_layer": clear.tolist(),
         "clear_disagreement_by_layer": clear_differ.tolist(),
         "ceiling": CLEAR_DISAGREEMENT, "routed_alike": routed_alike,
         "float8": float8, "no_erase": no_erase, "head_decay": head_decay,
         "logits_max_err_over_scale": float(
             onp.abs(got - want).max() / onp.abs(want).max())}), flush=True)
    if not routed_alike:
        got = onp.full_like(got, onp.nan)
    return got, want


def build_train(sizes, seed, global_batch, mesh=None, shard_optimizer=False):
    """Weights and the resident row from ``seed``; returns a dict with the
    net, the ``DataParallelStep``, ``run()`` (one step on the resident
    row, returns the loss NDArray) and ``check(**controls)`` (system
    logits and reference logits at seeded positions, taken BEFORE the
    first step).  The learning rate rises linearly over
    ``train["warmup_steps"]`` steps: the window's steps are the job's
    steps 4 and later."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel

    mx.random.seed(seed)
    onp.random.seed(seed)
    rs = onp.random.RandomState(seed)
    train = sizes["train"]
    net = _net(sizes)
    # the embedding first, at its own width (config.json: assumed.init);
    # what is initialised stays as it is
    net.embed.initialize(mx.init.Normal(train["embed_init_sigma"]))
    net.initialize(mx.init.Normal(train["init_sigma"]))
    net.cast(sizes["dtype"])
    net.collect_params().reset_ctx(mx.tpu())
    tokens, labels = draw_tokens(sizes, rs, global_batch)

    def on_device(arr):
        return mx.nd.array(arr.astype("int32"), ctx=mx.tpu(), dtype="int32")

    def put(arr):
        nd = on_device(arr)
        return parallel.shard_batch(nd, mesh) if mesh is not None else nd

    data, label = put(tokens), put(labels)
    opt = mx.optimizer.Adam(
        learning_rate=train["learning_rate"],
        multi_precision=train["multi_precision"],
        lr_scheduler=mx.lr_scheduler.FactorScheduler(
            step=1 << 40, warmup_steps=train["warmup_steps"]))
    loss = gluon.loss.TiedSoftmaxCrossEntropyLoss(
        block_rows=train["loss_block_rows"])
    step = parallel.DataParallelStep(net, loss, opt, mesh=mesh,
                                     shard_optimizer=shard_optimizer)

    def check(**controls):
        positions = draw_positions(sizes, rs, global_batch)
        # eager, on the chip (the default context is the host's CPU)
        with mx.tpu():
            logits = net(on_device(tokens), on_device(positions))
        chosen = onp.stack([layer.experts.last_expert.asnumpy()
                            for layer in net.layers if layer.kind == "sparse"])
        return compare(logits.asnumpy(), chosen, host_params(net), tokens,
                       positions, sizes, **controls)

    return {"net": net, "step": step, "check": check, "tokens": tokens,
            "labels": labels, "run": lambda: step(data, label)}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _layer_fn(sizes, index, rounded=None, no_erase=False, head_decay=False):
    """Published layer ``index`` of one row, jitted: ``(x (S, E), layer
    parameters[, follow]) -> (x, s, picked)`` with ``s`` (S, experts) the
    router's sigmoid scores of a sparse layer and ``picked`` its ``s + b``
    (both None of a dense layer).  A token's experts are the 8 largest of ``picked``;
    where ``follow`` (S, k) is given the experts run on those sets instead
    (the gates stay the layer's own scores of them, renormalised over the
    set) — see the module's docstring.  ``rounded``: a function put on
    every activation a matrix product reads or writes (the lower-precision
    control rounds there; the reference itself has none).  ``no_erase`` /
    ``head_decay``: the two other controls."""
    import jax
    import jax.numpy as jnp

    rnd = rounded or (lambda x: x)
    eps = sizes["rms_norm_eps"]
    mixer, ffn = _kinds(sizes, index)
    heads, d = sizes["num_attention_heads"], sizes["head_dim"]
    width = heads * d

    def rms(x, gamma):
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gamma

    def gated(h, gate, up, down):
        return rnd(jax.nn.silu(h @ gate) * (h @ up)) @ down

    # ---- Kimi Delta Attention, the recurrence one token at a time
    taps, bound = sizes["short_conv_kernel_size"], sizes["kda_lower_bound"]

    def kda(u, p):
        s = u.shape[0]
        proj = rnd(u @ p["kda_proj_weight"].T)
        qkv = proj[:, :3 * width]
        w = p["kda_conv_weight"]                           # (3 H d, 1, K)
        conv = sum(jnp.concatenate([jnp.zeros_like(qkv[:taps - 1 - j]),
                                    qkv[:s - (taps - 1 - j)]], axis=0)
                   * w[:, 0, j] for j in range(taps))
        qkv = jax.nn.silu(conv).reshape(s, 3, heads, d)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6)
        k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
        f = proj[:, 3 * width:4 * width].reshape(s, heads, d) \
            + p["kda_dt_bias"].reshape(heads, d)
        g = bound * jax.nn.sigmoid(jnp.exp(p["kda_a_log"])[:, None] * f)
        alpha = jnp.exp(g)
        if head_decay:
            alpha = jnp.broadcast_to(alpha.mean(-1, keepdims=True),
                                     alpha.shape)
        beta = jax.nn.sigmoid(proj[:, 4 * width:4 * width + heads])

        def step(state, t):
            q_t, k_t, v_t, a_t, b_t = t
            kept = a_t[..., None] * state                  # Diag(alpha) S
            if no_erase:
                erase = jnp.zeros_like(v_t)
            else:
                erase = jnp.einsum("hd,hdv->hv", k_t, kept)
            state = kept + b_t[:, None, None] * k_t[..., None] \
                * (v_t - erase)[:, None, :]
            return state, jnp.einsum("hdv,hd->hv", state, q_t) / d ** 0.5

        _, o = jax.lax.scan(step, jnp.zeros((heads, d, d)),
                            (q, k, v, alpha, beta))
        o = rms(o, p["kda_out_norm_gamma"].reshape(heads, d))
        o = o * jax.nn.sigmoid(proj[:, 4 * width + heads:])[..., None]
        return rnd(rnd(o.reshape(s, width)) @ p["kda_out_weight"].T)

    # ---- latent attention, published [nope | rope] order
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    v_dim, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    qk = nope + rope
    freq = jnp.asarray(sizes["rope_theta"] ** (
        -onp.arange(rope // 2, dtype="float64") * 2.0 / rope), jnp.float32)

    def interleaved(x):      # (S, h, nope + rope): pairs (2i, 2i + 1)
        ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freq
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        even, odd = x[..., nope::2], x[..., nope + 1::2]
        turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                           axis=-1).reshape(x.shape[:-1] + (rope,))
        return jnp.concatenate([x[..., :nope], turned], axis=-1)

    def mla(h, p):
        s = h.shape[0]
        q = rnd(h @ p["mla_q_weight"].T).reshape(s, heads, qk)
        kv_a = rnd(h @ p["mla_kv_a_weight"].T)
        latent = rnd(rms(kv_a[:, :rank], p["mla_kv_norm_gamma"]))
        kv = rnd(latent @ p["mla_kv_b_weight"].T).reshape(
            s, heads, nope + v_dim)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            kv_a[:, None, rank:], (s, heads, rope))], axis=-1)
        v = kv[..., nope:].transpose(1, 0, 2)
        q = interleaved(rms(q, p["mla_q_norm_gamma"])).transpose(1, 0, 2)
        k = interleaved(rms(k, p["mla_k_norm_gamma"])).transpose(1, 0, 2)
        rows_a_block = min(QUERY_ROWS, s)

        def rows(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, rows_a_block, axis=1)
            scores = jnp.einsum("hqd,hkd->hqk", qb, k) / (qk ** 0.5)
            seen = (start + jnp.arange(rows_a_block))[:, None] \
                >= jnp.arange(s)[None, :]
            prob = jax.nn.softmax(
                jnp.where(seen[None], scores, -1e30), axis=-1)
            return jnp.einsum("hqk,hkd->hqd", prob, v)

        out = jax.lax.map(rows, jnp.arange(0, s, rows_a_block))
        out = out.transpose(0, 2, 1, 3).reshape(s, heads, v_dim)
        gate = jax.nn.sigmoid(h @ p["mla_gate_weight"].T)       # (S, h)
        out = rnd((out * gate[:, :, None]).reshape(s, heads * v_dim))
        return rnd(out @ p["mla_out_weight"].T)

    # ---- group-limited sigmoid routing with a correction bias
    k_routes = sizes["num_experts_per_tok"]
    scale = sizes["routed_scaling_factor"]
    groups, kept_groups = sizes["n_group"], sizes["topk_group"]
    held_from, held_to = sizes["deployment"]["experts_held"]

    def experts(h, p, follow):
        score = jax.nn.sigmoid(h @ p["router_weight"].T)        # (S, E)
        pick = score + p["experts_balance_bias"]
        s, n = pick.shape
        by_group = pick.reshape(s, groups, n // groups)
        top2 = -jnp.sort(-by_group, axis=-1)[..., :2].sum(-1)   # (S, G)
        rank = jnp.argsort(-top2, axis=-1, stable=True)
        kept = (rank[:, :kept_groups, None]
                == jnp.arange(groups)[None, None, :]).any(1)
        picked = jnp.where(kept[:, :, None], by_group,
                           -jnp.inf).reshape(s, n)
        chosen = jnp.argsort(-picked, axis=-1, stable=True)[:, :k_routes] \
            if follow is None else follow
        got = jnp.take_along_axis(score, chosen, axis=1)
        gates = scale * got / (got.sum(-1, keepdims=True) + 1e-20)
        out = jnp.zeros_like(h)
        for e in range(held_from, held_to):
            j = e - held_from
            y = gated(h, p["experts_gate_weight"][j],
                      p["experts_up_weight"][j], p["experts_down_weight"][j])
            gate = jnp.where(chosen == e, gates, 0.0).sum(-1)
            out = out + gate[:, None] * y
        shared = gated(h, p["shared_gate_weight"].T, p["shared_up_weight"].T,
                       p["shared_down_weight"].T)
        return rnd(out + shared), score, pick

    def layer(x, p, follow=None):
        u = rnd(rms(x, p["%s_norm_gamma" % mixer]))
        x = rnd(x + (kda(u, p) if mixer == "kda" else mla(u, p)))
        h = rnd(rms(x, p["ffn_norm_gamma"]))
        if ffn == "sparse":
            out, score, picked = experts(h, p, follow)
            return rnd(x + out), score, picked
        out = gated(h, p["ffn_gate_weight"].T, p["ffn_up_weight"].T,
                    p["ffn_down_weight"].T)
        return rnd(x + rnd(out)), None, None

    return jax.jit(layer)


def _layer_params(params, index):
    pre = "layer%d_" % index
    return {name[len(pre):]: value for name, value in params.items()
            if name.startswith(pre)}


def reference_hidden(params, tokens, sizes, follow=None, rounded=None,
                     no_erase=False, head_decay=False):
    """The final normed hidden states (B, S, E), and every sparse layer's
    router scores and ``s + b`` (sparse layers, B, S, experts each),
    as numpy arrays.  ``follow`` (sparse layers, B, S, k): the sets to run
    the experts on instead of the reference's own.  One layer's weights
    are on the device at a time, one row goes through at a time."""
    import jax
    import jax.numpy as jnp

    eps = sizes["rms_norm_eps"]
    sparse = _sparse_layers(sizes)
    with jax.default_matmul_precision("highest"):
        xs = [jnp.asarray(params["embed_weight"][row]) for row in tokens]
        scores, picks = [], []
        for i in _kept(sizes):
            fn = _layer_fn(sizes, i, rounded=rounded, no_erase=no_erase,
                           head_decay=head_decay)
            lp = {name: jnp.asarray(value)
                  for name, value in _layer_params(params, i).items()}
            rows, chosen = [], []
            for b in range(len(xs)):
                sets = None if follow is None or i not in sparse else \
                    jnp.asarray(follow[sparse.index(i)][b], jnp.int32)
                xs[b], s, picked = fn(xs[b], lp, sets)
                if s is not None:
                    rows.append(onp.asarray(s))
                    chosen.append(onp.asarray(picked))
            if rows:
                scores.append(onp.stack(rows))
                picks.append(onp.stack(chosen))
            del lp
        gamma = jnp.asarray(params["final_norm_gamma"])
        hidden = [x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gamma
                  for x in xs]
        return onp.stack([onp.asarray(h) for h in hidden]), \
            onp.stack(scores), onp.stack(picks)


def reference_logits(params, hidden, positions):
    """Logits (B, P, V) of the untied head at ``positions`` (B, P)."""
    import jax
    import jax.numpy as jnp

    picked = onp.take_along_axis(hidden, positions[:, :, None], axis=1)
    with jax.default_matmul_precision("highest"):
        return onp.asarray(jax.jit(lambda h, w: h @ w.T)(
            jnp.asarray(picked), jnp.asarray(params["head_weight"])))


def reference_forward(params, tokens, positions, sizes):
    """Plain float32 forward: the logits (B, P, V) over the rows of the
    vocabulary held here at ``positions`` (B, P) of each row.  ``params``
    maps the zoo's parameter names (without the model prefix) to float32
    arrays."""
    hidden, _, _ = reference_hidden(params, tokens, sizes)
    return reference_logits(params, hidden, onp.asarray(positions))


def reference_loss_and_grads(params, tokens, labels, sizes, follow=None):
    """The next-token cross-entropy — the mean over the positions whose
    label is not -1, over the rows of the vocabulary held — and its
    gradient for every trained parameter, float32, through the same plain
    layers (recomputed in the backward: ``jax.checkpoint``).  ``follow``
    (sparse layers, B, S, k), where given, are the sets to run the experts
    on instead of the reference's own."""
    import jax
    import jax.numpy as jnp

    eps = sizes["rms_norm_eps"]
    sparse = _sparse_layers(sizes)
    kept = _kept(sizes)
    layers = [jax.checkpoint(_layer_fn(sizes, i)) for i in kept]
    state = ("experts_balance_bias", "experts_expert_load",
             "experts_rows_computed")
    trained = {k: v for k, v in params.items() if not k.endswith(state)}
    fixed = {k: v for k, v in params.items() if k.endswith(state)}

    def row_sum(trained, tokens, label, chosen):
        x = trained["embed_weight"][tokens]
        for i, layer in zip(kept, layers):
            sets = None if chosen is None or i not in sparse \
                else chosen[sparse.index(i)]
            x, _, _ = layer(x, dict(_layer_params(trained, i),
                                    **_layer_params(fixed, i)), sets)
        h = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
            * trained["final_norm_gamma"]
        logp = jax.nn.log_softmax(h @ trained["head_weight"].T, axis=-1)
        got = jnp.take_along_axis(logp, jnp.maximum(label, 0)[:, None],
                                  axis=1)[:, 0]
        return jnp.where(label >= 0, -got, 0.0).sum()

    count = float((onp.asarray(labels) >= 0).sum())
    with jax.default_matmul_precision("highest"):
        grad_fn = jax.jit(jax.value_and_grad(row_sum))
        on_device = {k: jnp.asarray(v) for k, v in trained.items()}
        fixed = {k: jnp.asarray(v) for k, v in fixed.items()}
        loss, grads = 0.0, None
        for b in range(len(tokens)):
            chosen = None if follow is None \
                else jnp.asarray(follow[:, b], jnp.int32)
            value, g = grad_fn(on_device, jnp.asarray(tokens[b], jnp.int32),
                               jnp.asarray(labels[b], jnp.int32), chosen)
            loss += float(value) / count
            g = {k: onp.asarray(v) / count for k, v in g.items()}
            grads = g if grads is None else \
                {k: grads[k] + g[k] for k in g}
    return loss, grads


# ---------------------------------------------------------------------------
# operations, from shapes
# ---------------------------------------------------------------------------

def _layer_counts(sizes):
    """(delta attention layers, latent layers, sparse layers, dense
    layers) of the cut."""
    kinds = [_kinds(sizes, i) for i in _kept(sizes)]
    return (sum(m == "kda" for m, _ in kinds), sum(m == "mla" for m, _ in kinds),
            sum(f == "sparse" for _, f in kinds),
            sum(f == "dense" for _, f in kinds))


def delta_chunk_macs(sizes):
    """Multiply-adds of the chunked delta rule's products for ONE chunk of
    one head, forward (``ops.delta_rule``): the two decayed C x C
    matrices built a sub-chunk of L keys at a time from the sub-chunk's
    first query row on (``2 d_k L sum_b (C - b L)``), the unit
    lower-triangular solve for ``[U~ | W]`` by forward substitution
    (``C (C - 1) / 2 (d_k + d_v)``), the state update (``W S`` and ``K^T
    U``: ``2 C d_k d_v``) and the output (``(Gamma o Q) S`` and ``A U``:
    ``C d_k d_v + C^2 d_v``).  ``W S`` is counted once, though the scan and
    the output each form it."""
    c, sub = sizes["delta_rule"]["chunk"], sizes["delta_rule"]["subchunk"]
    dk = dv = sizes["head_dim"]
    intra = 2 * dk * sub * sum(c - b * sub for b in range(c // sub))
    solve = c * (c - 1) // 2 * (dk + dv)
    return intra + solve + 2 * c * dk * dv + c * dk * dv + c * c * dv


def delta_scan_flops(sizes):
    """The floating-point operations of the chunked delta rule's products
    in one step of one row (``delta_chunk_macs`` x 2 a multiply-add, x the
    chunks of ``seq_len``, the heads and the delta attention layers), x 3
    for forward and backward (the backward counted at twice the forward,
    as ``model_flops`` counts every product; its recomputation of the
    forward is left out).  Left out too: the decays' exponentials and
    cumulative sums, the convolution, the norms, the gates, the
    projections (``linear_attention``'s)."""
    c = sizes["delta_rule"]["chunk"]
    chunks = -(-sizes["seq_len"] // c)
    kda_layers = _layer_counts(sizes)[0]
    return 3 * 2 * delta_chunk_macs(sizes) * chunks \
        * sizes["num_attention_heads"] * kda_layers


def causal_pairs(sizes):
    """The (query, key) pairs a causal layer leaves live in one row of
    ``seq_len`` tokens, a head: ``S (S + 1) / 2``."""
    s = sizes["seq_len"]
    return s * (s + 1) // 2


def causal_attention_flops(sizes):
    """The floating-point operations the three plain causal flash kernels
    (``flash_stream_fwd``, ``flash_dq``, ``flash_dkv``) do on LIVE pairs
    in one step of one row: the latent layers' calls, all heads, ``S (S +
    1) / 2`` pairs a head; a pair's products against q . k are counted over
    ``qk_nope_head_dim + qk_rope_head_dim`` (192) — scores in the forward,
    scores and dq in ``flash_dq``, scores and dk in ``flash_dkv``: ``10
    D_qk`` — and those against v over ``v_head_dim`` (128) — the values in
    the forward, dP in ``flash_dq``, dP and dv in ``flash_dkv``: ``8
    D_v``.  The zeros v is padded with to 192 for the kernels and the
    tiles' pairs above the diagonal are not counted, so a share of the
    peak made from this cannot pass 100%."""
    d_qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    mla_layers = _layer_counts(sizes)[1]
    return (10 * d_qk + 8 * sizes["v_head_dim"]) * causal_pairs(sizes) \
        * sizes["num_attention_heads"] * mla_layers


def moe_product_flops(sizes, routes):
    """The floating-point operations of the routed experts' products for
    ``routes`` routes to held experts (a step's, every layer's): ``2 D F``
    for each of the three products of a gated expert (gate, up, down),
    times 3 — forward, input gradient, weight gradient.  Left out: the
    recomputing backward's second forward, the slots a block holds beyond
    the routes, the shared expert, the router, the gathers and sums."""
    return 3 * 3 * 2 * sizes["hidden_size"] \
        * sizes["moe_intermediate_size"] * routes


def model_flops(sizes):
    """Floating-point operations one ROW of ``seq_len`` tokens needs,
    forward and backward, from the shapes alone: matrix multiplications
    only (2 per multiply-add), the backward pass twice the forward, no
    recomputation; the delta rule's products at the published chunk
    (``delta_scan_flops``); latent attention's scores and values at the
    causal pairs only (q . k over 192, p . v over 128); the routed experts
    at the share of the routes that an even router sends to the experts
    held (held / all, ``num_experts_per_tok`` routes a token); the head
    over the rows of the vocabulary held.  Left out: norms, the
    convolutions, rotary, softmax, gates' sigmoids and exponentials, the
    sort and gathers round the experts."""
    e, heads, d = sizes["hidden_size"], sizes["num_attention_heads"], \
        sizes["head_dim"]
    tokens, width = sizes["seq_len"], heads * d
    experts = sizes["published"]["num_experts"]
    held = sizes["num_experts"] / experts
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    v_dim, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    # multiply-adds a token
    kda = e * (4 * width + 2 * heads) + width * e
    mla = e * heads * (nope + rope) + e * (rank + rope) \
        + rank * heads * (nope + v_dim) + e * heads + heads * v_dim * e
    sparse = e * experts + 3 * e * (
        sizes["moe_shared_expert_intermediate_size"]
        + sizes["num_experts_per_tok"] * held
        * sizes["moe_intermediate_size"])
    dense = 3 * e * sizes["intermediate_size"]
    kda_layers, mla_layers, sparse_layers, dense_layers = _layer_counts(sizes)
    total = tokens * (e * sizes["vocab_size"] + kda_layers * kda
                      + mla_layers * mla + sparse_layers * sparse
                      + dense_layers * dense) \
        + mla_layers * (nope + rope + v_dim) * causal_pairs(sizes) * heads
    return 3 * 2 * total + delta_scan_flops(sizes)
