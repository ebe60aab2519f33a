"""ZAYA1-8B, one chip's share of a two-chip expert-parallel deployment: the
builder through the system's normal path (``gluon.model_zoo.zaya1`` ->
``DataParallelStep`` with ``Adam(multi_precision=True)``), the plain
reference, and the FLOP count.

The reference is float32 ``jax.numpy`` at ``highest`` matmul precision,
written from the equations in ``config.json``'s ``assumed`` and sharing no
code with the system: dense loops over the experts, full S x S scores (in
blocks of query rows so that they fit), no kernels, no sort.  It is given
the same share as the system: the experts and the rows of the vocabulary
that ``deployment`` says are held here.

Top-1 routing is an argmax, and an argmax is discontinuous: where two
experts tie within what bfloat16 resolves the system and the float32
reference pick differently for about one token in a hundred, the two
answers differ by a whole expert's output at that token, and through
attention a little at every later one.  So the comparison that decides
``correct`` has three parts (``compare``).  The logits are compared with the
reference FOLLOWING the system's routing choices (the gate stays the
reference's own probability of that expert).  The choices themselves have
to AGREE with the reference's own for at least ``ROUTING_AGREEMENT`` of the
tokens of every layer.  And where the reference's choice is CLEAR — its two
best experts further apart than ``CLEAR_GAP`` of the spread of the layer's
probabilities, forty times what a bfloat16 rounding moves them — no more
than ``CLEAR_DISAGREEMENT`` of the tokens may be routed otherwise: a wrong
route is not a tie.
"""
import json

import numpy as onp

QUERY_ROWS = 512      # the reference's attention: query rows a block
# The limits of ``compare``, each between two readings on the chip (PERF.md,
# PR 26): bfloat16 over nine seeds, and the reference with every activation
# a matrix product reads or writes rounded to float8 (e4m3, a scale a row).
ROUTING_AGREEMENT = 0.96     # of a layer's tokens: 0.9855-0.9993 / 0.923
CLEAR_GAP = 0.1              # of the rms deviation of p from 1 / experts
CLEAR_DISAGREEMENT = 0.005   # of the clearly routed tokens: 0 / 0.0163

# The block kinds of the step's device time (``reduce_trace.kind_of``): a
# pattern is searched in each ``Block.name`` scope of an operation's
# ``op_name``, the outermost match decides.  A pre-norm goes with the block
# it feeds.
# The compiler's own ``ragged-dot-*`` kernels (``lax.ragged_dot`` in
# ``ops.moe.grouped_matmul``, the experts' products) carry no scope
# and are matched by their name.
BLOCKS = {
    "attention": [r"_layer\d+_(cca|attn_norm)$"],
    "experts": [r"_layer\d+_(experts|router|moe_norm)$",
                r"^ragged-dot-"],
    "head": [r"_embed$", r"_final_norm$",
             r"^tiedsoftmaxcrossentropyloss\d+$"],
}


def _net(sizes):
    from mxnet_tpu.gluon.model_zoo import zaya1

    return zaya1(
        vocab_size=sizes["vocab_size"], units=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"],
        expert_hidden=sizes["moe_intermediate_size"],
        num_experts=sizes["published"]["num_experts"],
        router_hidden=sizes["router_hidden_size"],
        experts_held=tuple(sizes["deployment"]["experts_held"]),
        conv_kernels=(sizes["cca_time0"], sizes["cca_time1"]),
        partial_rotary_factor=sizes["partial_rotary_factor"],
        rope_theta=sizes["rope_parameters"]["hybrid"]["rope_theta"],
        epsilon=sizes["rms_norm_eps"],
        bias_update_rate=sizes["train"]["bias_update_rate"])


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


def host_params(net):
    """The net's parameters as float32 numpy arrays, by the zoo's names
    without the model's prefix."""
    return {name[len(net.prefix):]: onp.asarray(
        p.data().asnumpy()).astype("float32")
        for name, p in net.collect_params().items()}


def compare(logits, chosen, params, tokens, positions, sizes):
    """What ``correct.logits_agree`` is handed: the logits (B, P, V) of a
    forward whose layers routed the tokens to ``chosen`` (layers, B, S), and
    the reference's at the same ``positions`` with its experts run on those
    choices.  Where the routing itself fails one of its two limits (the
    module's docstring) the logits handed on are NaN: no verdict."""
    hidden, probs = reference_hidden(params, tokens, sizes, follow=chosen)
    layers = range(sizes["num_hidden_layers"])
    bias = onp.stack([params["layer%d_experts_balance_bias" % i]
                      for i in layers])[:, None, None, :]
    best = onp.sort(probs + bias, axis=-1)
    gap = best[..., -1] - best[..., -2]
    spread = onp.sqrt(((probs - 1.0 / probs.shape[-1]) ** 2).mean(
        axis=(1, 2, 3), keepdims=True))[..., 0]
    clear = gap > CLEAR_GAP * spread
    differ = (probs + bias).argmax(-1) != chosen
    agreement = 1.0 - differ.mean(axis=(1, 2))
    clear_differ = (differ & clear).sum(axis=(1, 2)) / clear.sum(axis=(1, 2))
    routed_alike = bool(agreement.min() >= ROUTING_AGREEMENT
                        and clear_differ.max() <= CLEAR_DISAGREEMENT)
    got = onp.asarray(logits, "float32")
    want = reference_logits(params, hidden, positions)
    print("[check] %s" % json.dumps(
        {"routing_agreement_by_layer": agreement.tolist(),
         "floor": ROUTING_AGREEMENT,
         "clear_share_by_layer": clear.mean(axis=(1, 2)).tolist(),
         "clear_disagreement_by_layer": clear_differ.tolist(),
         "ceiling": CLEAR_DISAGREEMENT, "routed_alike": routed_alike,
         "logits_max_err_over_scale": float(
             onp.abs(got - want).max() / onp.abs(want).max())}), flush=True)
    if not routed_alike:
        got = onp.full_like(got, onp.nan)
    return got, want


def build_train(sizes, seed, global_batch, mesh=None, shard_optimizer=False):
    """Weights and the resident batch from ``seed``; returns a dict with
    the net, the ``DataParallelStep``, ``run()`` (one step on the resident
    batch, returns the loss NDArray) and ``check()`` (system logits and
    reference logits at seeded positions, taken BEFORE the first step).
    The learning rate rises linearly over ``train["warmup_steps"]``
    steps: the window's steps are the job's steps 4 and later."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel

    mx.random.seed(seed)
    onp.random.seed(seed)
    rs = onp.random.RandomState(seed)
    train = sizes["train"]
    net = _net(sizes)
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

    def check():
        positions = onp.sort(onp.stack(
            [rs.choice(sizes["seq_len"], train["check_positions_per_row"],
                       replace=False) for _ in range(global_batch)]), 1)
        # eager, on the chip (the default context is the host's CPU)
        with mx.tpu():
            logits = net(on_device(tokens), on_device(positions))
        chosen = onp.stack([layer.experts.last_expert.asnumpy()
                            for layer in net.layers])
        return compare(logits.asnumpy(), chosen, host_params(net), tokens,
                       positions, sizes)

    return {"net": net, "step": step, "check": check,
            "run": lambda: step(data, label)}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _layer_fn(sizes, first_layer, rounded=None):
    """One layer of one row, jitted: ``(x (S, E), r_prev (S, R) or None,
    layer parameters[, follow]) -> (x, r, p (S, experts))``.  A token's
    expert is ``argmax(p + b)``; where ``follow`` (S,) is given the experts
    run on those choices instead (the gate stays the layer's own
    probability of that expert) — see the module's docstring.  ``rounded``:
    a function put on every activation a matrix product reads or writes
    (the lower-precision control rounds there; the reference itself has
    none)."""
    import jax
    import jax.numpy as jnp

    rnd = rounded or (lambda x: x)

    heads, kv_heads = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"]
    d, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    group = heads // kv_heads
    rotary = int(d * sizes["partial_rotary_factor"])
    theta = float(sizes["rope_parameters"]["hybrid"]["rope_theta"])
    held_from, held_to = sizes["deployment"]["experts_held"]

    def rms(x, gamma):
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gamma

    def before(x):                       # x_(t-1), zero at t = 0
        return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)

    def rope(x):                         # (H, S, d)
        half = rotary // 2
        freq = theta ** (-jnp.arange(half) * 2.0 / rotary)
        ang = jnp.arange(x.shape[1])[:, None] * freq[None, :]
        a, b, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
        return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                                b * jnp.cos(ang) + a * jnp.sin(ang), rest],
                               axis=-1)

    def unit(x):                         # sqrt(d) x / |x|
        return x * (d ** 0.5) / jnp.sqrt(
            (x * x).sum(-1, keepdims=True) + 1e-12)

    def cca(h, p):
        s = h.shape[0]
        q_lat = rnd(h @ p["cca_q_weight"].T)
        k_lat = rnd(h @ p["cca_k_weight"].T)
        z = jnp.concatenate([q_lat, k_lat], axis=-1)
        w0 = p["cca_conv0_weight"]                        # (C, 1, 2)
        z = z * w0[:, 0, 1] + before(z) * w0[:, 0, 0]
        w1 = p["cca_conv1_weight"]                        # (C, d, 2)
        zg, zb = z.reshape(s, -1, d), before(z).reshape(s, -1, d)
        wg = w1.reshape(-1, d, d, 2)                      # (G, out, in, K)
        z = (jnp.einsum("sgi,goi->sgo", zg, wg[..., 1])
             + jnp.einsum("sgi,goi->sgo", zb, wg[..., 0])).reshape(s, -1)
        q4 = q_lat.reshape(s, kv_heads, group, d)
        mq = (q4 + k_lat.reshape(s, kv_heads, 1, d)) / 2
        q = z[:, :heads * d] + mq.reshape(s, -1)
        k = z[:, heads * d:] + mq.mean(2).reshape(s, -1)
        q = rope(unit(q.reshape(s, heads, d).transpose(1, 0, 2)))
        k = unit(k.reshape(s, kv_heads, d).transpose(1, 0, 2)) \
            * p["cca_k_scale"][:, None, None]
        k = rope(k)
        v = rnd(jnp.stack([h @ p["cca_v_now_weight"].T,
                           before(h) @ p["cca_v_prev_weight"].T]))  # (2, S, d)
        q, k = rnd(q), rnd(k)
        k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)

        def rows(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
            scores = jnp.einsum("hqd,hkd->hqk", qb, k) / (d ** 0.5)
            seen = (start + jnp.arange(block))[:, None] \
                >= jnp.arange(s)[None, :]
            scores = jnp.where(seen[None], scores, -jnp.inf)
            return jnp.einsum("hqk,hkd->hqd",
                              jax.nn.softmax(scores, axis=-1), v)

        block = min(QUERY_ROWS, s)
        out = jax.lax.map(rows, jnp.arange(0, s, block))  # (n, H, blk, d)
        out = rnd(out.transpose(0, 2, 1, 3).reshape(s, heads * d))
        return rnd(out @ p["cca_out_weight"].T)

    def moe(h, r_prev, p, follow):
        r = rnd(h @ p["router_down_weight"].T)
        if not first_layer:
            r = r + p["router_depth_gamma"] * r_prev
        t = rnd(rms(r, p["router_norm_gamma"]))
        for name in ("router_fc1_weight", "router_fc2_weight"):
            t = rnd(jax.nn.gelu(t @ p[name].T, approximate=False))
        probs = jax.nn.softmax(t @ p["router_fc3_weight"].T, axis=-1)
        expert = jnp.argmax(probs + p["experts_balance_bias"], axis=-1) \
            if follow is None else follow
        gate = jnp.take_along_axis(probs, expert[:, None], axis=1)
        out = jnp.zeros_like(h)
        for e in range(held_from, held_to):
            i = e - held_from
            y = rnd(jax.nn.silu(h @ p["experts_gate_weight"][i])
                    * (h @ p["experts_up_weight"][i])) \
                @ p["experts_down_weight"][i]
            out = out + jnp.where((expert == e)[:, None], gate * y, 0.0)
        return rnd(out), r, probs

    def layer(x, r_prev, p, follow=None):
        x = rnd(x + cca(rnd(rms(x, p["attn_norm_gamma"])), p))
        out, r, probs = moe(rnd(rms(x, p["moe_norm_gamma"])), r_prev, p,
                            follow)
        return rnd(x + out), r, probs

    return jax.jit(layer)


def _layer_params(params, i):
    pre = "layer%d_" % i
    return {name[len(pre):]: value for name, value in params.items()
            if name.startswith(pre)}


def reference_hidden(params, tokens, sizes, follow=None, rounded=None):
    """The final normed hidden states (B, S, E) and every layer's router
    probabilities (layers, B, S, experts), as numpy arrays.  ``follow``
    (layers, B, S): the choices to run the experts on instead of the
    reference's own.  ``rounded``: see ``_layer_fn``.  One layer's weights
    are on the device at a time, one row goes through at a time."""
    import jax
    import jax.numpy as jnp

    eps = sizes["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        xs = [jnp.asarray(params["embed_weight"][row]) for row in tokens]
        rs = [None] * len(xs)
        probs = []
        for i in range(sizes["num_hidden_layers"]):
            fn = _layer_fn(sizes, first_layer=i == 0, rounded=rounded)
            lp = {name: jnp.asarray(value)
                  for name, value in _layer_params(params, i).items()}
            rows = []
            for b in range(len(xs)):
                xs[b], rs[b], p = fn(
                    xs[b], rs[b], lp, None if follow is None
                    else jnp.asarray(follow[i][b], jnp.int32))
                rows.append(onp.asarray(p))
            probs.append(onp.stack(rows))
            del lp
        gamma = jnp.asarray(params["final_norm_gamma"])
        hidden = [x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)
                  * gamma for x in xs]
        return onp.stack([onp.asarray(h) for h in hidden]), onp.stack(probs)


def reference_logits(params, hidden, positions):
    """Logits (B, P, V) of the tied head at ``positions`` (B, P)."""
    import jax
    import jax.numpy as jnp

    picked = onp.take_along_axis(hidden, positions[:, :, None], axis=1)
    table = params["embed_weight"]
    with jax.default_matmul_precision("highest"):
        head = jax.jit(lambda h, w: h @ w.T)
        rows = 16384
        parts = [onp.asarray(head(jnp.asarray(picked),
                                  jnp.asarray(table[i:i + rows])))
                 for i in range(0, table.shape[0], rows)]
    return onp.concatenate(parts, axis=-1)


def reference_forward(params, tokens, positions, sizes):
    """Plain float32 forward: the logits (B, P, V) over the rows of the
    vocabulary held here at ``positions`` (B, P) of each row of ``tokens``
    (B, S).  ``params`` maps the zoo's parameter names (without the model
    prefix) to float32 arrays."""
    hidden, _ = reference_hidden(params, tokens, sizes)
    return reference_logits(params, hidden, onp.asarray(positions))


def reference_loss_and_grads(params, tokens, labels, sizes, follow=None):
    """The training loss (mean next-token cross-entropy over the positions
    whose label is not -1, per row, then over rows) and its gradient for
    every float parameter, float32, through the same plain layers.  For
    the tests and the scratch comparison on the chip: layers and the head
    are recomputed in the backward (``jax.checkpoint``), the head in
    blocks of 1,024 tokens, so that the timed sizes fit.  ``follow``
    (layers, B, S), where given, are the routing choices to run the
    experts on instead of the reference's own (see ``_layer_fn``)."""
    import jax
    import jax.numpy as jnp

    eps = sizes["rms_norm_eps"]
    layers = [jax.checkpoint(_layer_fn(sizes, first_layer=i == 0))
              for i in range(sizes["num_hidden_layers"])]
    state = ("experts_balance_bias", "experts_expert_load",
             "experts_rows_computed")
    trained = {k: v for k, v in params.items() if not k.endswith(state)}
    fixed = {k: jnp.asarray(v) for k, v in params.items()
             if k.endswith(state)}

    @jax.checkpoint
    def head_block(h, table, lab):
        logp = jax.nn.log_softmax(h @ table.T, axis=-1)
        got = jnp.take_along_axis(logp, jnp.maximum(lab, 0)[:, None],
                                  axis=1)[:, 0]
        return jnp.where(lab >= 0, -got, 0.0)

    def row_loss(trained, row, lab, chosen):
        p = dict(trained, **fixed)
        x, r = p["embed_weight"][row], None
        for i, layer in enumerate(layers):
            x, r, _ = layer(x, r, _layer_params(p, i),
                            None if chosen is None else chosen[i])
        h = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
            * p["final_norm_gamma"]
        block = min(1024, h.shape[0])
        losses = jax.lax.map(
            lambda hl: head_block(hl[0], p["embed_weight"], hl[1]),
            (h.reshape(-1, block, h.shape[-1]), lab.reshape(-1, block)))
        return losses.sum() / (lab >= 0).sum()

    # a row at a time (the rows meet only in the mean): half the memory
    with jax.default_matmul_precision("highest"):
        grad_fn = jax.jit(jax.value_and_grad(row_loss))
        on_device = {k: jnp.asarray(v) for k, v in trained.items()}
        loss, grads = 0.0, None
        for b, (row, lab) in enumerate(zip(tokens, labels)):
            chosen = None if follow is None \
                else jnp.asarray(follow[:, b], jnp.int32)
            value, g = grad_fn(on_device, jnp.asarray(row, jnp.int32),
                               jnp.asarray(lab, jnp.int32), chosen)
            loss += float(value) / len(tokens)
            g = {k: onp.asarray(v) / len(tokens) for k, v in g.items()}
            grads = g if grads is None else \
                {k: grads[k] + g[k] for k in g}
    return loss, grads


def causal_attention_flops(sizes):
    """The floating-point operations the three causal flash kernels
    (``flash_stream_fwd``, ``flash_dq``, ``flash_dkv``) execute ON LIVE
    PAIRS in one step of one row: every layer, all its query heads, ``S (S
    + 1) / 2`` pairs a head, ``18 D`` a pair — ``4 D`` forward (scores,
    values), ``6 D`` in ``flash_dq`` (scores again, dP, dq), ``8 D`` in
    ``flash_dkv`` (scores again, dP, dk, dv).  The tiles' pairs above the
    diagonal are not counted, so a share of the peak made from this cannot
    pass 100%; CCA's convolutions, projections and norms are not kernels'
    work and are left out."""
    s = sizes["seq_len"]
    return 18 * sizes["head_dim"] * s * (s + 1) // 2 \
        * sizes["num_attention_heads"] * sizes["num_hidden_layers"]


def moe_product_flops(sizes, routes):
    """The floating-point operations of the routed experts' products for
    ``routes`` routes to held experts (a step's, every layer's): ``2 D F``
    for each of the three products of a gated expert (gate, up, down),
    times 3 — forward, input gradient, weight gradient.  Left out: the
    rows past the last group that a ragged product may visit, the router,
    the sort, gathers and sums."""
    return 3 * 3 * 2 * sizes["hidden_size"] \
        * sizes["moe_intermediate_size"] * routes


def model_flops(sizes):
    """Floating-point operations one ROW of ``seq_len`` tokens needs,
    forward and backward, from the shapes alone: matrix multiplications
    only (2 per multiply-add), the backward pass twice the forward, no
    recomputation; the causal scores at half the square; the experts at the
    share of the tokens that an even router sends to the experts held
    (held / all); the head over the rows of the vocabulary held."""
    e, s, d = sizes["hidden_size"], sizes["seq_len"], sizes["head_dim"]
    q_width = sizes["num_attention_heads"] * d
    kv_width = sizes["num_key_value_heads"] * d
    r = sizes["router_hidden_size"]
    experts = sizes["published"]["num_experts"]
    held = sizes["num_experts"] / experts
    cca = e * (q_width + 2 * kv_width) + q_width * e \
        + sizes["cca_time1"] * (q_width + kv_width) * d
    scores = 2 * q_width * s / 2
    router = e * r + 2 * r * r + r * experts
    expert = held * 3 * e * sizes["moe_intermediate_size"]
    layer = cca + scores + router + expert
    head = e * sizes["vocab_size"]
    return 3 * 2 * s * (sizes["num_hidden_layers"] * layer + head)
