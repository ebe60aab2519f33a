"""Nemotron-3-Nano-30B-A3B, one chip's share of a 16-chip expert-parallel
deployment: the builder through the system's normal path
(``gluon.model_zoo.nemotron_h`` -> ``DataParallelStep`` with
``Adam(multi_precision=True)``), the plain reference, and the FLOP count.

The reference is float32 ``jax.numpy`` at ``highest`` matmul precision,
written from the equations in ``config.json``'s ``assumed`` and sharing no
code with the system: the state-space recurrence runs STEP BY STEP
(``lax.scan`` over t; the system computes it in chunks), the experts are a
dense loop, attention forms full S x S scores (in blocks of query rows so
that they fit), no kernels, no sort.  It is given the same share as the
system: the experts and the rows of the vocabulary that ``deployment`` says
are held here, and the shared expert once.

A top-6 choice is discontinuous: where a token's 6th and 7th scores tie
within what bfloat16 resolves, the system and the float32 reference pick
different sets, the two answers differ by a whole expert's output at that
token, and through the later layers a little everywhere.  So the comparison
that decides ``correct`` has three parts (``compare``).  The logits are
compared with the reference FOLLOWING the system's chosen sets (the gates
stay the reference's own scores of those experts).  The choices themselves
have to AGREE with the reference's own: at least ``ROUTING_AGREEMENT`` of
every layer's routes go where the reference sends them.  And where the
reference's choice is CLEAR — its 6th and 7th ``s + b`` further apart than
``CLEAR_GAP`` of the layer's standard deviation of ``s`` — no more than
``CLEAR_DISAGREEMENT`` of the tokens may have another set: a wrong route is
not a tie.  The scan has no limit of its own, and the logits do not hold
its precision: the reference with its state rounded to bfloat16 after
every step reads 2.0% of the largest logit on the chip, under the 3%
(PERF.md section 6, PR 30) — only the slowest heads drift.  What holds the
float32 state is the operator's test on the slowest head at S = 8192
(``tests/test_nemotron_h.py``).
"""
import json

import numpy as onp

QUERY_ROWS = 512      # the reference's attention: query rows a block
SCAN_BLOCK = 128      # the reference's backward re-runs the recurrence
#                       from every SCAN_BLOCK-th state (memory, not algebra)
# The limits of ``compare``, each between two readings on the chip (PERF.md
# section 6, PR 30): bfloat16 over eight seeds, and the reference with every
# activation a matrix product reads or writes rounded to float8 (e4m3, a
# scale a row).
ROUTING_AGREEMENT = 0.96     # of a layer's routes: 0.9838-0.9939 / 0.874-0.937
CLEAR_GAP = 0.1              # of the standard deviation of a layer's s
CLEAR_DISAGREEMENT = 0.005   # of the clearly routed tokens: 0 / 0.025-0.291

# The block kinds of the step's device time (``reduce_trace.kind_of``): a
# pattern is searched in each ``Block.name`` scope of an operation's
# ``op_name``, the outermost match decides.  A layer's one pre-norm goes
# with its mixer: by the kept pattern ``MEMEM*EME`` the Mamba-2 mixers are
# layers 0, 2, 4, 7, the expert layers 1, 3, 6, 8, attention layer 5.
# The compiler's own ``ragged-dot-*`` kernels (``lax.ragged_dot`` in
# ``ops.moe.grouped_matmul``, which only the experts call) carry no scope
# and are matched by their name.
BLOCKS = {
    "mixer": [r"_layer\d+_mamba$", r"_layer[0247]_norm$"],
    "experts": [r"_layer\d+_(experts|router)$", r"_layer[1368]_norm$",
                r"^ragged-dot-"],
    "attention": [r"_layer\d+_attn$", r"_layer5_norm$"],
    "ffn": [r"_layer\d+_shared$"],
    "head": [r"_embed$", r"_final_norm$",
             r"^tiedsoftmaxcrossentropyloss\d+$"],
}


def _net(sizes):
    from mxnet_tpu.gluon.model_zoo import nemotron_h

    layers = sizes["num_hidden_layers"]
    return nemotron_h(
        pattern=sizes["hybrid_override_pattern"][:layers],
        vocab_size=sizes["vocab_size"], units=sizes["hidden_size"],
        mamba_heads=sizes["mamba_num_heads"],
        mamba_head_dim=sizes["mamba_head_dim"],
        state_size=sizes["ssm_state_size"], num_groups=sizes["n_groups"],
        conv_kernel=sizes["conv_kernel"], chunk_size=sizes["chunk_size"],
        dt_range=(sizes["time_step_min"], sizes["time_step_max"]),
        dt_floor=sizes["time_step_floor"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"],
        num_experts=sizes["published"]["n_routed_experts"],
        experts_per_token=sizes["num_experts_per_tok"],
        expert_hidden=sizes["moe_intermediate_size"],
        shared_hidden=sizes["moe_shared_expert_intermediate_size"],
        routed_scale=sizes["routed_scaling_factor"],
        experts_held=tuple(sizes["deployment"]["experts_held"]),
        bias_update_rate=sizes["train"]["bias_update_rate"],
        epsilon=sizes["layer_norm_epsilon"])


def _kinds(sizes):
    return sizes["hybrid_override_pattern"][:sizes["num_hidden_layers"]]


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


def routing_agreement(scores, bias, chosen, k):
    """How the chosen sets (layers, B, S, k) stand to the reference's own,
    the ``k`` largest of ``scores + bias`` (layers, B, S, experts), a layer
    at a time: the share of routes the reference has too, the share of
    tokens it routes clearly, and the share of THOSE whose set differs."""
    picked = scores + bias
    ranked = onp.argsort(-picked, axis=-1, kind="stable")
    own = onp.sort(ranked[..., :k], axis=-1)
    ordered = onp.take_along_axis(picked, ranked[..., :k + 1], axis=-1)
    gap = ordered[..., k - 1] - ordered[..., k]
    clear = gap > CLEAR_GAP * scores.std(axis=(1, 2, 3))[:, None, None]
    shared = (onp.sort(chosen, axis=-1)[..., :, None]
              == own[..., None, :]).any(-1).sum(-1)
    agreement = shared.mean(axis=(1, 2)) / k
    differ = shared < k
    return agreement, clear.mean(axis=(1, 2)), \
        (differ & clear).sum(axis=(1, 2)) / onp.maximum(
            clear.sum(axis=(1, 2)), 1)


def compare(logits, chosen, params, tokens, positions, sizes):
    """What ``correct.logits_agree`` is handed: the logits (B, P, V) of a
    forward whose expert layers routed the tokens to ``chosen`` (expert
    layers, B, S, k), and the reference's at the same ``positions`` with
    its experts run on those sets.  Where the routing itself fails one of
    its two limits (the module's docstring) the logits handed on are NaN:
    no verdict."""
    hidden, scores = reference_hidden(params, tokens, sizes, follow=chosen)
    expert_layers = [i for i, kind in enumerate(_kinds(sizes))
                     if kind == "E"]
    bias = onp.stack([params["layer%d_experts_balance_bias" % i]
                      for i in expert_layers])[:, None, None, :]
    agreement, clear, clear_differ = routing_agreement(
        scores, bias, chosen, sizes["num_experts_per_tok"])
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
                            for layer in net.layers if layer.kind == "E"])
        return compare(logits.asnumpy(), chosen, host_params(net), tokens,
                       positions, sizes)

    return {"net": net, "step": step, "check": check,
            "run": lambda: step(data, label)}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _layer_fn(sizes, kind, rounded=None, state_rounded=None):
    """One layer of one row, jitted: ``(x (S, E), layer parameters[,
    follow]) -> (x, s)`` with ``s`` (S, experts) the router's scores of an
    ``E`` layer (None otherwise).  A token's experts are the 6 largest of
    ``s + b``; where ``follow`` (S, k) is given the experts run on those
    sets instead (the gates stay the layer's own scores of them) — see the
    module's docstring.  ``rounded``: a function put on every activation a
    matrix product reads or writes (the lower-precision control rounds
    there; the reference itself has none); ``state_rounded``: one put on
    the recurrence's state after every step (the control of the scan's
    precision)."""
    import jax
    import jax.numpy as jnp

    rnd = rounded or (lambda x: x)
    state_rnd = state_rounded or (lambda x: x)
    eps = sizes["layer_norm_epsilon"]

    def rms(x, gamma):
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gamma

    def relu2(x):
        return jnp.maximum(x, 0.0) ** 2

    # ---- M: the Mamba-2 mixer, the recurrence one step at a time
    heads, p_dim = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    groups, n_dim = sizes["n_groups"], sizes["ssm_state_size"]
    inner, bc = heads * p_dim, groups * n_dim
    taps = sizes["conv_kernel"]

    def mamba(u, p):
        s = u.shape[0]
        zxbcdt = rnd(u @ p["mamba_in_weight"].T)
        z = zxbcdt[:, :inner]
        xbc = zxbcdt[:, inner:2 * inner + 2 * bc]
        dt = zxbcdt[:, 2 * inner + 2 * bc:]
        w = p["mamba_conv_weight"]                        # (C, 1, K)
        conv = p["mamba_conv_bias"] + sum(
            jnp.concatenate([jnp.zeros_like(xbc[:taps - 1 - j]),
                             xbc[:s - (taps - 1 - j)]], axis=0) * w[:, 0, j]
            for j in range(taps))
        xbc = rnd(jax.nn.silu(conv))
        x = xbc[:, :inner].reshape(s, groups, heads // groups, p_dim)
        b = xbc[:, inner:inner + bc].reshape(s, groups, n_dim)
        c = xbc[:, inner + bc:].reshape(s, groups, n_dim)
        delta = jax.nn.softplus(dt + p["mamba_dt_bias"]).reshape(
            s, groups, heads // groups)
        a = -jnp.exp(p["mamba_a_log"]).reshape(groups, heads // groups)
        skip = p["mamba_d_skip"].reshape(groups, heads // groups)

        def step(h, t):
            x_t, b_t, c_t, d_t = t
            h = state_rnd(
                jnp.exp(d_t * a)[..., None, None] * h
                + (d_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
            return h, jnp.einsum("grpn,gn->grp", h, c_t) \
                + skip[..., None] * x_t

        def block(h, ts):
            return jax.lax.scan(step, h, ts)

        blk = next(n for n in range(min(SCAN_BLOCK, s), 0, -1) if s % n == 0)
        _, y = jax.lax.scan(
            jax.checkpoint(block),
            jnp.zeros((groups, heads // groups, p_dim, n_dim)),
            tuple(t.reshape((s // blk, blk) + t.shape[1:])
                  for t in (x, b, c, delta)))
        y = rnd(y.reshape(s, inner)) * jax.nn.silu(z)
        y = y.reshape(s, groups, inner // groups)
        y = y / jnp.sqrt((y * y).mean(-1, keepdims=True) + eps)
        y = rnd(y.reshape(s, inner) * p["mamba_norm_gamma"])
        return rnd(y @ p["mamba_out_weight"].T)

    # ---- *: causal grouped-query attention, no position embedding
    q_heads, kv_heads = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"]
    d = sizes["head_dim"]

    def attention(h, p):
        s = h.shape[0]
        qkv = rnd(h @ p["attn_qkv_weight"].T)
        q = qkv[:, :q_heads * d].reshape(s, q_heads, d).transpose(1, 0, 2)
        k, v = (qkv[:, (q_heads + i * kv_heads) * d:
                    (q_heads + (i + 1) * kv_heads) * d].reshape(
            s, kv_heads, d).transpose(1, 0, 2) for i in (0, 1))
        k, v = (jnp.repeat(t, q_heads // kv_heads, axis=0) for t in (k, v))
        block = min(QUERY_ROWS, s)

        def rows(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
            scores = jnp.einsum("hqd,hkd->hqk", qb, k) / (d ** 0.5)
            seen = (start + jnp.arange(block))[:, None] \
                >= jnp.arange(s)[None, :]
            scores = jnp.where(seen[None], scores, -jnp.inf)
            return jnp.einsum("hqk,hkd->hqd",
                              jax.nn.softmax(scores, axis=-1), v)

        out = jax.lax.map(rows, jnp.arange(0, s, block))  # (n, H, blk, d)
        out = rnd(out.transpose(0, 2, 1, 3).reshape(s, q_heads * d))
        return rnd(out @ p["attn_out_weight"].T)

    # ---- E: top-k sigmoid-routed relu^2 experts beside a shared expert
    k_routes = sizes["num_experts_per_tok"]
    held_from, held_to = sizes["deployment"]["experts_held"]

    def experts(h, p, follow):
        s = jax.nn.sigmoid(h @ p["router_weight"].T)      # (S, experts)
        chosen = jnp.argsort(-(s + p["experts_balance_bias"]), axis=-1,
                             stable=True)[:, :k_routes] \
            if follow is None else follow
        picked = jnp.take_along_axis(s, chosen, axis=1)
        gates = sizes["routed_scaling_factor"] * picked / (
            picked.sum(-1, keepdims=True) + 1e-20)
        out = rnd(relu2(h @ p["shared_fc1_weight"].T)) \
            @ p["shared_fc2_weight"].T
        for e in range(held_from, held_to):
            i = e - held_from
            y = rnd(relu2(h @ p["experts_up_weight"][i])) \
                @ p["experts_down_weight"][i]
            gate = jnp.where(chosen == e, gates, 0.0).sum(-1)
            out = out + gate[:, None] * y
        return rnd(out), s

    def layer(x, p, follow=None):
        h = rnd(rms(x, p["norm_gamma"]))
        if kind == "M":
            return rnd(x + mamba(h, p)), None
        if kind == "*":
            return rnd(x + attention(h, p)), None
        out, s = experts(h, p, follow)
        return rnd(x + out), s

    return jax.jit(layer)


def _layer_params(params, i):
    pre = "layer%d_" % i
    return {name[len(pre):]: value for name, value in params.items()
            if name.startswith(pre)}


def reference_hidden(params, tokens, sizes, follow=None, rounded=None,
                     state_rounded=None):
    """The final normed hidden states (B, S, E) and every EXPERT layer's
    router scores (expert layers, B, S, experts), as numpy arrays.
    ``follow`` (expert layers, B, S, k): the sets to run the experts on
    instead of the reference's own.  ``rounded``, ``state_rounded``: see
    ``_layer_fn``.  One layer's weights are on the device at a time, one
    row goes through at a time."""
    import jax
    import jax.numpy as jnp

    eps = sizes["layer_norm_epsilon"]
    with jax.default_matmul_precision("highest"):
        xs = [jnp.asarray(params["embed_weight"][row]) for row in tokens]
        scores = []
        for i, kind in enumerate(_kinds(sizes)):
            fn = _layer_fn(sizes, kind, rounded=rounded,
                           state_rounded=state_rounded)
            lp = {name: jnp.asarray(value)
                  for name, value in _layer_params(params, i).items()}
            rows = []
            for b in range(len(xs)):
                sets = None if follow is None or kind != "E" else \
                    jnp.asarray(follow[len(scores)][b], jnp.int32)
                xs[b], s = fn(xs[b], lp, sets)
                rows.append(s)
            if kind == "E":
                scores.append(onp.stack([onp.asarray(s) for s in rows]))
            del lp
        gamma = jnp.asarray(params["final_norm_gamma"])
        hidden = [x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)
                  * gamma for x in xs]
        return onp.stack([onp.asarray(h) for h in hidden]), onp.stack(scores)


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
    vocabulary held here at ``positions`` (B, P) of each row of ``tokens``
    (B, S).  ``params`` maps the zoo's parameter names (without the model
    prefix) to float32 arrays."""
    hidden, _ = reference_hidden(params, tokens, sizes)
    return reference_logits(params, hidden, onp.asarray(positions))


def reference_loss_and_grads(params, tokens, labels, sizes, follow=None):
    """The training loss (mean next-token cross-entropy over the positions
    whose label is not -1, per row, then over rows) and its gradient for
    every trained parameter, float32, through the same plain layers
    (recomputed in the backward: ``jax.checkpoint``).  ``follow`` (expert
    layers, B, S, k), where given, are the sets to run the experts on
    instead of the reference's own (see ``_layer_fn``)."""
    import jax
    import jax.numpy as jnp

    eps = sizes["layer_norm_epsilon"]
    kinds = _kinds(sizes)
    layers = [jax.checkpoint(_layer_fn(sizes, kind)) for kind in kinds]
    state = ("experts_balance_bias", "experts_expert_load",
             "experts_rows_computed")
    trained = {k: v for k, v in params.items() if not k.endswith(state)}
    fixed = {k: jnp.asarray(v) for k, v in params.items()
             if k.endswith(state)}

    def row_loss(trained, row, lab, chosen):
        p = dict(trained, **fixed)
        x, seen = p["embed_weight"][row], 0
        for i, (kind, layer) in enumerate(zip(kinds, layers)):
            sets = None
            if kind == "E":
                sets, seen = (None if chosen is None else chosen[seen]), \
                    seen + 1
            x, _ = layer(x, _layer_params(p, i), sets)
        h = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
            * p["final_norm_gamma"]
        logp = jax.nn.log_softmax(h @ p["head_weight"].T, axis=-1)
        got = jnp.take_along_axis(logp, jnp.maximum(lab, 0)[:, None],
                                  axis=1)[:, 0]
        return jnp.where(lab >= 0, -got, 0.0).sum() / (lab >= 0).sum()

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
    PAIRS in one step of one row: every attention layer (``*``), all its
    query heads, ``S (S + 1) / 2`` pairs a head, ``18 D`` a pair — ``4 D``
    forward (scores, values), ``6 D`` in ``flash_dq`` (scores again, dP,
    dq), ``8 D`` in ``flash_dkv`` (scores again, dP, dk, dv).  The tiles'
    pairs above the diagonal are not counted, so a share of the peak made
    from this cannot pass 100%."""
    s = sizes["seq_len"]
    return 18 * sizes["head_dim"] * s * (s + 1) // 2 \
        * sizes["num_attention_heads"] * _kinds(sizes).count("*")


def moe_product_flops(sizes, routes):
    """The floating-point operations of the routed experts' products for
    ``routes`` routes to held experts (a step's, every layer's): ``2 D F``
    for each of the two products of a relu² expert (up, down), times 3 —
    forward, input gradient, weight gradient.  Left out: the recomputing
    backward's second forward, the slots a block holds beyond the routes,
    the shared expert, the router, the gathers and sums."""
    return 3 * 2 * 2 * sizes["hidden_size"] \
        * sizes["moe_intermediate_size"] * routes


def ssd_flops(sizes):
    """The floating-point operations of the chunked scan's products in one
    step of one row, every Mamba-2 layer (``M``), at the published chunk
    Q: a chunk's scores ``C B^T`` once a group (``2 Q^2 N``), and a head's
    in-chunk product with x (``2 Q^2 P``), its chunk state ``B^T x`` and
    the carried state's output ``C h`` (``2 Q N P`` each); ``S / Q`` chunks;
    times 3 — forward, and the two products of each one's gradient.  Left
    out: the decays, the cumulative sums and the states passed from chunk
    to chunk (element-wise), the states the backward makes again
    (``ssd_states``), the skip ``D x``, the gate and the norm."""
    s, q = sizes["seq_len"], min(sizes["chunk_size"], sizes["seq_len"])
    heads, p_dim = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    n_dim, groups = sizes["ssm_state_size"], sizes["n_groups"]
    chunk = 2 * groups * q * q * n_dim \
        + heads * (2 * q * q * p_dim + 4 * q * n_dim * p_dim)
    return 3 * chunk * (s // q) * _kinds(sizes).count("M")


def model_flops(sizes):
    """Floating-point operations one ROW of ``seq_len`` tokens needs,
    forward and backward, from the shapes alone: matrix multiplications
    only (2 per multiply-add), the backward pass twice the forward, no
    recomputation; the causal attention scores at half the square; the
    routed experts at the share of the routes that an even router sends
    to the experts held (held / all, ``num_experts_per_tok`` routes a
    token); the scan's products by the chunked form's shapes (scores and
    in-chunk product at the whole (Q, Q) block, chunk states, the carried
    state's output); the head over the rows of the vocabulary held.  Left
    out: the convolution's taps, norms, gates, softmax, the decays and the
    cumulative sums, the sort and gathers round the experts."""
    e, s = sizes["hidden_size"], sizes["seq_len"]
    heads, p_dim = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    n_dim, groups = sizes["ssm_state_size"], sizes["n_groups"]
    chunk = min(sizes["chunk_size"], s)
    inner = heads * p_dim
    # multiply-adds a token
    mamba = e * (2 * inner + 2 * groups * n_dim + heads) + inner * e \
        + groups * chunk * n_dim + heads * chunk * p_dim \
        + 2 * heads * p_dim * n_dim
    q_width = sizes["num_attention_heads"] * sizes["head_dim"]
    kv_width = sizes["num_key_value_heads"] * sizes["head_dim"]
    attention = e * (q_width + 2 * kv_width) + q_width * e \
        + 2 * q_width * s / 2
    experts = sizes["published"]["n_routed_experts"]
    held = sizes["n_routed_experts"] / experts
    moe = e * experts \
        + 2 * e * sizes["moe_shared_expert_intermediate_size"] \
        + sizes["num_experts_per_tok"] * held * 2 * e \
        * sizes["moe_intermediate_size"]
    per_kind = {"M": mamba, "*": attention, "E": moe}
    layers = sum(per_kind[kind] for kind in _kinds(sizes))
    head = e * sizes["vocab_size"]
    return 3 * 2 * s * (layers + head)
