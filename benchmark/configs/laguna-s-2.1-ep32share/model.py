"""Laguna-S-2.1, one chip's share of a 32-chip expert-parallel deployment:
the builder through the system's normal path (``gluon.model_zoo.laguna``
-> ``DataParallelStep`` with ``Adam(multi_precision=True)`` and the blocked
cross-entropy over the untied head), the plain reference, and the FLOP
counts.

The reference is float32 ``jax.numpy`` at ``highest`` matmul precision,
written from the equations in ``config.json``'s ``assumed`` and sharing no
code with the system: attention forms the full scores of a block of query
rows against ALL keys under a dense boolean mask built from the rules
(``dense_mask``: a key not after the query; on a sliding layer not more
than ``sliding_window - 1`` before it — the system never builds it: it
hands the kernels three integers a query), the two rotary embeddings come
from the closed forms (``rotary_frequencies``), the head gate, the dense
block, the shared expert and the experts are plain products, the experts a
dense loop, no kernels, no sort.  It is given the same share as the
system: the experts and the rows of the vocabulary that ``deployment``
says are held here.

A top-10 choice is discontinuous: where a token's 10th and 11th
probabilities tie within what bfloat16 resolves, the system and the
float32 reference pick different sets and the two answers differ by a
whole expert's output at that token.  So ``compare`` has three parts, as
the other decoder cells': the logits are compared with the reference
FOLLOWING the system's chosen sets (the gates stay the reference's own
probabilities of them); at least ``ROUTING_AGREEMENT`` of every layer's
routes go where the reference sends them; and of the tokens the reference
routes CLEARLY (its 10th and 11th probabilities further apart than
``CLEAR_GAP`` of the layer's standard deviation of p) at most
``CLEAR_DISAGREEMENT`` have another set.  Three controls have to fail
(PERF.md section 6, PR 37, has every reading): the reference in float8;
the reference with the window's lower bound dropped (``drop_window``: the
sliding layers causal); the reference with the default rotary rule on the
full layers (``default_rotary``: no YaRN blend, no ``attention_factor``).
Half the compared positions lie past token ``check_positions_past``, where
a dropped window shows.
"""
import json
import math

import numpy as onp

QUERY_ROWS = 512      # the reference's attention: query rows a block
# The limits of ``compare``, each between two readings on the chip (PERF.md
# section 6, PR 37): bfloat16 over the seeds tried, and the reference with
# every activation a matrix product reads or writes rounded to float8
# (e4m3, a scale a row).
ROUTING_AGREEMENT = 0.96     # of a layer's routes
CLEAR_GAP = 0.1              # of the standard deviation of a layer's p
CLEAR_DISAGREEMENT = 0.005   # of the clearly routed tokens

FULL = "full_attention"

# The block kinds of the step's device time (``reduce_trace.kind_of``): a
# pattern is searched in each ``Block.name`` scope of an operation's
# ``op_name``, the outermost match decides.  A pre-norm goes with the block
# it feeds; a sparse layer's feed-forward norm with the router, which reads
# it first.  Layer 0 is the one dense layer of the cut (``mlp_layer_types``).
# The compiler's own ``ragged-dot-*`` kernels (``lax.ragged_dot`` in
# ``ops.moe.grouped_matmul``, which only the experts call) carry no scope
# and are matched by their name.
BLOCKS = {
    "attention": [r"_layer\d+_attn(_norm)?$"],
    "experts": [r"_layer\d+_(experts|router)$", r"_layer[1-9]\d*_ffn_norm$",
                r"^ragged-dot-"],
    "ffn": [r"_layer0_ffn(_norm)?$", r"_layer\d+_shared$"],
    "head": [r"_embed$", r"_final_norm$",
             r"^tiedsoftmaxcrossentropyloss\d+$"],
}


def _rope(sizes, kind):
    """A layer kind's rotary embedding (``rope_parameters[kind]``) as
    ``ops.nn.rotary_embedding``'s keyword arguments."""
    given = sizes["rope_parameters"][kind]
    rope = dict(theta=float(given["rope_theta"]),
                rotary_dim=int(given["partial_rotary_factor"]
                               * sizes["head_dim"]))
    if given["rope_type"] != "default":
        rope.update(
            rope_type=given["rope_type"], factor=float(given["factor"]),
            original_length=given["original_max_position_embeddings"],
            beta_fast=float(given["beta_fast"]),
            beta_slow=float(given["beta_slow"]),
            attention_factor=float(given["attention_factor"]))
    return rope


def _net(sizes):
    from mxnet_tpu.gluon.model_zoo import laguna

    layers = sizes["num_hidden_layers"]
    return laguna(
        vocab_size=sizes["vocab_size"], units=sizes["hidden_size"],
        num_layers=layers, layer_types=sizes["layer_types"][:layers],
        heads_per_layer=sizes["num_attention_heads_per_layer"][:layers],
        mlp_layer_types=sizes["mlp_layer_types"][:layers],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], window=sizes["sliding_window"],
        full_rope=_rope(sizes, FULL),
        sliding_rope=_rope(sizes, "sliding_attention"),
        dense_hidden=sizes["intermediate_size"],
        num_experts=sizes["published"]["num_experts"],
        experts_per_token=sizes["num_experts_per_tok"],
        expert_hidden=sizes["moe_intermediate_size"],
        shared_hidden=sizes["shared_expert_intermediate_size"],
        routed_scale=sizes["moe_routed_scaling_factor"],
        experts_held=tuple(sizes["deployment"]["experts_held"]),
        epsilon=sizes["rms_norm_eps"])


def _sparse_layers(sizes):
    return [i for i, kind in enumerate(
        sizes["mlp_layer_types"][:sizes["num_hidden_layers"]])
        if kind == "sparse"]


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


def routing_agreement(probs, chosen, k):
    """How the chosen sets (layers, B, S, k) stand to the reference's own,
    the ``k`` largest of ``probs`` (layers, B, S, experts), a layer at a
    time: the share of routes the reference has too, the share of tokens
    it routes clearly, and the share of THOSE whose set differs."""
    ranked = onp.argsort(-probs, axis=-1, kind="stable")
    own = onp.sort(ranked[..., :k], axis=-1)
    ordered = onp.take_along_axis(probs, ranked[..., :k + 1], axis=-1)
    gap = ordered[..., k - 1] - ordered[..., k]
    clear = gap > CLEAR_GAP * probs.std(axis=(1, 2, 3))[:, None, None]
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
            drop_window=False, default_rotary=False):
    """What ``correct.logits_agree`` is handed: the logits (B, P, V) of a
    forward whose sparse layers routed the tokens to ``chosen`` (sparse
    layers, B, S, k), and the reference's at the same ``positions`` with
    its experts run on those sets.  Where the routing itself fails one of
    its two limits (the module's docstring) the logits handed on are NaN:
    no verdict.  ``float8``, ``drop_window`` and ``default_rotary`` are the
    three controls, each of which has to come out as not correct."""
    hidden, probs = reference_hidden(
        params, tokens, sizes, follow=chosen,
        rounded=float8_rounded if float8 else None,
        drop_window=drop_window, default_rotary=default_rotary)
    agreement, clear, clear_differ = routing_agreement(
        probs, chosen, sizes["num_experts_per_tok"])
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
         "float8": float8, "drop_window": drop_window,
         "default_rotary": default_rotary,
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
        chosen = onp.stack([net.layers[i].experts.last_expert.asnumpy()
                            for i in _sparse_layers(sizes)])
        return compare(logits.asnumpy(), chosen, host_params(net), tokens,
                       positions, sizes, **controls)

    return {"net": net, "step": step, "check": check, "tokens": tokens,
            "labels": labels, "run": lambda: step(data, label)}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def dense_mask(rows, length, window=None):
    """The mask from its rules, for the queries ``rows`` against all
    ``length`` keys, (len(rows), length) bool: query i sees key j iff
    ``j <= i`` and, under a ``window``, ``j > i - window`` (``window``
    keys, the query's own included)."""
    import jax.numpy as jnp

    i, j = rows[:, None], jnp.arange(length)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)
    return seen


def rotary_frequencies(given, head_dim, default=False):
    """``(freq (r / 2,), factor on cos and sin, r)`` of one
    ``rope_parameters`` group, float64 numpy, from the closed forms in
    ``config.json``'s ``assumed.rotary``.  ``default``: the group's theta
    and width under the default rule (the control)."""
    r = int(given["partial_rotary_factor"] * head_dim)
    theta = float(given["rope_theta"])
    i = onp.arange(r // 2, dtype="float64")
    inv = theta ** (-2.0 * i / r)
    if default or given["rope_type"] == "default":
        return inv, 1.0, r

    def corr(turns):
        return r * math.log(given["original_max_position_embeddings"]
                            / (2 * math.pi * turns)) / (2 * math.log(theta))
    low = max(math.floor(corr(given["beta_fast"])), 0)
    high = min(math.ceil(corr(given["beta_slow"])), r - 1)
    ramp = onp.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    freq = inv * (1 - ramp) + inv / given["factor"] * ramp
    return freq, float(given["attention_factor"]), r


def _layer_fn(sizes, i, rounded=None, drop_window=False,
              default_rotary=False):
    """Layer ``i`` of one row, jitted: ``(x (S, E), layer parameters[,
    follow]) -> (x, p)`` with ``p`` (S, experts) the router's
    probabilities of a sparse layer (None of a dense one).  A token's
    experts are the 10 largest of ``p``; where ``follow`` (S, k) is given
    the experts run on those sets instead (the gates stay the layer's own
    probabilities of them, renormalised over the set) — see the module's
    docstring.  ``rounded``: a function put on every activation a matrix
    product reads or writes (the lower-precision control rounds there;
    the reference itself has none).  ``drop_window`` / ``default_rotary``:
    the two other controls."""
    import jax
    import jax.numpy as jnp

    rnd = rounded or (lambda x: x)
    eps, d = sizes["rms_norm_eps"], sizes["head_dim"]
    kind = sizes["layer_types"][i]
    q_heads = sizes["num_attention_heads_per_layer"][i]
    kv_heads = sizes["num_key_value_heads"]
    sparse = sizes["mlp_layer_types"][i] == "sparse"
    window = None if kind == FULL or drop_window \
        else sizes["sliding_window"]
    freq, factor, r = rotary_frequencies(
        sizes["rope_parameters"][kind], d,
        default=default_rotary and kind == FULL)
    freq = jnp.asarray(freq, jnp.float32)
    k_routes = sizes["num_experts_per_tok"]
    scale = sizes["moe_routed_scaling_factor"]
    held_from, held_to = sizes["deployment"]["experts_held"]

    def rms(x, gamma):
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gamma

    def rotate(x):             # x (S, h, d): rotate-half on the first r
        ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freq
        cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
        x1, x2 = x[..., :r // 2], x[..., r // 2:r]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                                x[..., r:]], -1)

    def gated(h, gate, up, down):
        return rnd(jax.nn.silu(h @ gate) * (h @ up)) @ down

    def attention(h, p):
        s = h.shape[0]
        qkv = rnd(h @ p["attn_qkv_weight"].T)
        q = qkv[:, :q_heads * d].reshape(s, q_heads, d)
        k, v = (qkv[:, (q_heads + j * kv_heads) * d:
                    (q_heads + (j + 1) * kv_heads) * d].reshape(
            s, kv_heads, d) for j in (0, 1))
        q, k = rotate(q).transpose(1, 0, 2), rotate(k).transpose(1, 0, 2)
        v = v.transpose(1, 0, 2)
        k, v = (jnp.repeat(t, q_heads // kv_heads, axis=0) for t in (k, v))
        rows_a_block = min(QUERY_ROWS, s)

        def rows(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, rows_a_block, axis=1)
            scores = jnp.einsum("hqd,hkd->hqk", qb, k) / (d ** 0.5)
            seen = dense_mask(start + jnp.arange(rows_a_block), s, window)
            prob = jax.nn.softmax(
                jnp.where(seen[None], scores, -1e30), axis=-1)
            return jnp.einsum("hqk,hkd->hqd", prob, v)

        out = jax.lax.map(rows, jnp.arange(0, s, rows_a_block))
        out = out.transpose(0, 2, 1, 3).reshape(s, q_heads, d)
        gate = jax.nn.sigmoid(h @ p["attn_gate_weight"].T)     # (S, h)
        out = rnd((out * gate[:, :, None]).reshape(s, q_heads * d))
        return rnd(out @ p["attn_out_weight"].T)

    def experts(h, p, follow):
        prob = jax.nn.softmax(h @ p["router_weight"].T, axis=-1)
        chosen = jnp.argsort(-prob, axis=-1, stable=True)[:, :k_routes] \
            if follow is None else follow
        picked = jnp.take_along_axis(prob, chosen, axis=1)
        gates = scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
        out = jnp.zeros_like(h)
        for e in range(held_from, held_to):
            j = e - held_from
            y = gated(h, p["experts_gate_weight"][j],
                      p["experts_up_weight"][j], p["experts_down_weight"][j])
            gate = jnp.where(chosen == e, gates, 0.0).sum(-1)
            out = out + gate[:, None] * y
        shared = gated(h, p["shared_gate_weight"].T, p["shared_up_weight"].T,
                       p["shared_down_weight"].T)
        return rnd(out + shared), prob

    def layer(x, p, follow=None):
        x = rnd(x + attention(rnd(rms(x, p["attn_norm_gamma"])), p))
        h = rnd(rms(x, p["ffn_norm_gamma"]))
        if sparse:
            out, prob = experts(h, p, follow)
            return rnd(x + out), prob
        out = gated(h, p["ffn_gate_weight"].T, p["ffn_up_weight"].T,
                    p["ffn_down_weight"].T)
        return rnd(x + rnd(out)), None

    return jax.jit(layer)


def _layer_params(params, i):
    pre = "layer%d_" % i
    return {name[len(pre):]: value for name, value in params.items()
            if name.startswith(pre)}


def reference_hidden(params, tokens, sizes, follow=None, rounded=None,
                     drop_window=False, default_rotary=False):
    """The final normed hidden states (B, S, E) and every sparse layer's
    router probabilities (sparse layers, B, S, experts), as numpy arrays.
    ``follow`` (sparse layers, B, S, k): the sets to run the experts on
    instead of the reference's own.  One layer's weights are on the device
    at a time, one row goes through at a time."""
    import jax
    import jax.numpy as jnp

    eps = sizes["rms_norm_eps"]
    sparse = _sparse_layers(sizes)
    with jax.default_matmul_precision("highest"):
        xs = [jnp.asarray(params["embed_weight"][row]) for row in tokens]
        probs = []
        for i in range(sizes["num_hidden_layers"]):
            fn = _layer_fn(sizes, i, rounded=rounded,
                           drop_window=drop_window,
                           default_rotary=default_rotary)
            lp = {name: jnp.asarray(value)
                  for name, value in _layer_params(params, i).items()}
            rows = []
            for b in range(len(xs)):
                sets = None if follow is None or i not in sparse else \
                    jnp.asarray(follow[sparse.index(i)][b], jnp.int32)
                xs[b], p = fn(xs[b], lp, sets)
                if p is not None:
                    rows.append(onp.asarray(p))
            if rows:
                probs.append(onp.stack(rows))
            del lp
        gamma = jnp.asarray(params["final_norm_gamma"])
        hidden = [x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gamma
                  for x in xs]
        return onp.stack([onp.asarray(h) for h in hidden]), onp.stack(probs)


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
    hidden, _ = reference_hidden(params, tokens, sizes)
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
    layers = [jax.checkpoint(_layer_fn(sizes, i))
              for i in range(sizes["num_hidden_layers"])]
    state = ("experts_balance_bias", "experts_expert_load",
             "experts_rows_computed", "mask_tiles")
    trained = {k: v for k, v in params.items() if not k.endswith(state)}

    def row_sum(trained, tokens, label, chosen):
        x = trained["embed_weight"][tokens]
        for i, layer in enumerate(layers):
            sets = None if chosen is None or i not in sparse \
                else chosen[sparse.index(i)]
            x, _ = layer(x, _layer_params(trained, i), sets)
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

def live_pairs(sizes, kind):
    """The (query, key) pairs a layer of ``kind`` leaves live in one row
    of ``seq_len`` tokens, a query head: the lower triangle ``S (S + 1) /
    2`` on a full layer; on a sliding one the band, query i's ``min(i + 1,
    W)`` keys: ``W (W + 1) / 2 + (S - W) W`` (an eighth of the square at
    S = 4,096 and W = 512)."""
    s = sizes["seq_len"]
    w = s if kind == FULL else min(sizes["sliding_window"], s)
    return w * (w + 1) // 2 + (s - w) * w


def _layers(sizes):
    n = sizes["num_hidden_layers"]
    return list(zip(sizes["layer_types"][:n],
                    sizes["num_attention_heads_per_layer"][:n],
                    sizes["mlp_layer_types"][:n]))


def attention_flops(sizes):
    """The floating-point operations the three MASKED flash kernels
    execute ON LIVE PAIRS in one step of one row — the sliding layers'
    calls, all their query heads; the full layers run the plain causal
    kernels and are not counted here: a live pair costs ``4 D`` in the
    forward (scores and values), ``6 D`` in ``flash_masked_dq`` (scores
    again, dP, dq) and ``8 D`` in ``flash_masked_dkv`` (scores again, dP,
    dk, dv), ``18 D`` in all.  Dead pairs are not counted, whatever of
    them a partial tile computes, so a share of the peak made from this
    cannot pass 100%.  Zero where the window covers the row (the sliding
    layers then run the causal kernels too)."""
    if sizes["sliding_window"] >= sizes["seq_len"]:
        return 0
    return sum(18 * sizes["head_dim"] * live_pairs(sizes, kind) * heads
               for kind, heads, _ in _layers(sizes) if kind != FULL)


def causal_attention_flops(sizes):
    """The floating-point operations the three plain causal flash kernels
    (``flash_stream_fwd``, ``flash_dq``, ``flash_dkv``) execute ON LIVE
    PAIRS in one step of one row: the full layers' calls, all their query
    heads, ``S (S + 1) / 2`` pairs a head, ``18 D`` a pair as in
    ``attention_flops``.  The tiles' pairs above the diagonal are not
    counted, so a share of the peak made from this cannot pass 100%.  The
    sliding layers run the masked kernels and are left out — unless the
    window covers the row, when they run these kernels too."""
    covers = sizes["sliding_window"] >= sizes["seq_len"]
    return sum(18 * sizes["head_dim"] * live_pairs(sizes, FULL) * heads
               for kind, heads, _ in _layers(sizes) if kind == FULL or covers)


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
    recomputation; the attention scores and values at the LIVE pairs only
    (``live_pairs``: causal on a full layer, the band on a sliding one);
    the routed experts at the share of the routes that an even router
    sends to the experts held (held / all, ``num_experts_per_tok`` routes
    a token); the head over the rows of the vocabulary held.  Left out:
    norms, rotary, softmax, gates' sigmoids, the sort and gathers round
    the experts."""
    e, d, tokens = sizes["hidden_size"], sizes["head_dim"], sizes["seq_len"]
    kv_width = sizes["num_key_value_heads"] * d
    experts = sizes["published"]["num_experts"]
    held = sizes["num_experts"] / experts
    sparse = e * experts + 3 * e * (
        sizes["shared_expert_intermediate_size"]
        + sizes["num_experts_per_tok"] * held
        * sizes["moe_intermediate_size"])
    total = tokens * e * sizes["vocab_size"]
    for kind, heads, ffn in _layers(sizes):
        # multiply-adds a token: [q | k | v], the head gate, o, the block
        layer = e * (heads * d + 2 * kv_width) + e * heads + heads * d * e \
            + (sparse if ffn == "sparse" else 3 * e
               * sizes["intermediate_size"])
        total += tokens * layer + 2 * d * live_pairs(sizes, kind) * heads
    return 3 * 2 * total
