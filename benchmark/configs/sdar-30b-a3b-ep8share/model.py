"""SDAR-30B-A3B-Chat trained by block diffusion, one chip's share of an
8-chip expert-parallel deployment: the builder through the system's normal
path (``gluon.model_zoo.sdar`` -> ``DataParallelStep`` with
``Adam(multi_precision=True)`` and ``gluon.loss.BlockDiffusionLoss``), the
plain reference, and the FLOP counts.

The reference is float32 ``jax.numpy`` at ``highest`` matmul precision,
written from the equations in ``config.json``'s ``assumed`` and sharing no
code with the system: attention forms the full scores of a block of query
rows against ALL 2L keys under a dense boolean mask built from the three
clauses of block diffusion (``dense_mask``; the system never builds it: it
hands the kernels two integers a token), the rotary angles come from the
position ids, the experts are a dense loop, no kernels, no sort.  It is
given the same share as the system: the experts and the rows of the
vocabulary that ``deployment`` says are held here.

A top-8 choice is discontinuous: where a token's 8th and 9th probabilities
tie within what bfloat16 resolves, the system and the float32 reference
pick different sets and the two answers differ by a whole expert's output
at that token.  So ``compare`` has three parts, as the two other decoder
cells': the logits are compared with the reference FOLLOWING the system's
chosen sets (the gates stay the reference's own probabilities of them); at
least ``ROUTING_AGREEMENT`` of every layer's routes go where the reference
sends them; and of the tokens the reference routes CLEARLY (its 8th and 9th
probabilities further apart than ``CLEAR_GAP`` of the layer's standard
deviation of p) at most ``CLEAR_DISAGREEMENT`` have another set.  One more
control holds the MASK: the reference with one clause dropped — noised
queries no longer see their own block — has to fail the logits' limit
(PERF.md section 6, PR 33, has every reading).
"""
import json

import numpy as onp

QUERY_ROWS = 512      # the reference's attention: query rows a block
# The limits of ``compare``, each between two readings on the chip (PERF.md
# section 6, PR 33): bfloat16 over the seeds tried, and the reference with
# every activation a matrix product reads or writes rounded to float8
# (e4m3, a scale a row).
ROUTING_AGREEMENT = 0.96     # of a layer's routes
CLEAR_GAP = 0.1              # of the standard deviation of a layer's p
CLEAR_DISAGREEMENT = 0.005   # of the clearly routed tokens

# The block kinds of the step's device time (``reduce_trace.kind_of``): a
# pattern is searched in each ``Block.name`` scope of an operation's
# ``op_name``, the outermost match decides.  A pre-norm goes with the block
# it feeds.
# The compiler's own ``ragged-dot-*`` kernels (``lax.ragged_dot`` in
# ``ops.moe.grouped_matmul``, which only the experts call) carry no scope
# and are matched by their name.
BLOCKS = {
    "attention": [r"_layer\d+_attn(_norm)?$"],
    "experts": [r"_layer\d+_(experts|router|ffn_norm)$",
                r"^ragged-dot-"],
    "head": [r"_embed$", r"_final_norm$", r"^blockdiffusionloss\d+$"],
}


def _net(sizes):
    from mxnet_tpu.gluon.model_zoo import sdar

    return sdar(
        vocab_size=sizes["vocab_size"], units=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], rope_theta=float(sizes["rope_theta"]),
        num_experts=sizes["published"]["num_experts"],
        experts_per_token=sizes["num_experts_per_tok"],
        expert_hidden=sizes["moe_intermediate_size"],
        experts_held=tuple(sizes["deployment"]["experts_held"]),
        epsilon=sizes["rms_norm_eps"])


def draw_row(sizes, rs, batch):
    """The step's traffic from ``rs``: ``batch`` rows of ``seq_len`` clean
    token ids with text-like frequencies (Zipf over the ids of the slice
    but the last, which is ``[MASK]``; id = rank), noised once into the
    2 x ``seq_len`` row, position ids, mask integers, labels and weights of
    ``gluon.model_zoo.block_diffusion_row``."""
    from mxnet_tpu.gluon.model_zoo import block_diffusion_row

    train = sizes["train"]
    mask_id = sizes["vocab_size"] - 1
    weight = (onp.arange(mask_id) + 1.0) ** -train["token_zipf_exponent"]
    tokens = rs.choice(mask_id, size=(batch, sizes["seq_len"]),
                       p=weight / weight.sum())
    return block_diffusion_row(tokens, train["block_length"], mask_id, rs,
                               t_min=train["noise_t_min"])


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


def compare(logits, chosen, params, row, positions, sizes, float8=False,
            drop_own_block=False):
    """What ``correct.logits_agree`` is handed: the logits (B, P, V) of a
    forward whose layers routed the tokens to ``chosen`` (layers, B, 2L,
    k), and the reference's at the same ``positions`` of the noised half
    with its experts run on those sets.  Where the routing itself fails
    one of its two limits (the module's docstring) the logits handed on
    are NaN: no verdict.  ``float8`` and ``drop_own_block`` are the two
    controls, each of which has to come out as not correct."""
    hidden, probs = reference_hidden(
        params, row, sizes, follow=chosen,
        rounded=float8_rounded if float8 else None,
        drop_own_block=drop_own_block)
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
         "float8": float8, "drop_own_block": drop_own_block,
         "logits_max_err_over_scale": float(
             onp.abs(got - want).max() / onp.abs(want).max())}), flush=True)
    if not routed_alike:
        got = onp.full_like(got, onp.nan)
    return got, want


def build_train(sizes, seed, global_batch, mesh=None, shard_optimizer=False):
    """Weights and the resident row from ``seed``; returns a dict with the
    net, the ``DataParallelStep``, ``run()`` (one step on the resident
    row, returns the loss NDArray) and ``check(**controls)`` (system
    logits and reference logits at seeded positions of the noised half,
    taken BEFORE the first step).  The noise is drawn ONCE, here: it is
    part of the traffic, and every step sees the same row.  The learning
    rate rises linearly over ``train["warmup_steps"]`` steps: the
    window's steps are the job's steps 4 and later."""
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
    row = draw_row(sizes, rs, global_batch)

    def on_device(arr, dtype="int32"):
        return mx.nd.array(arr.astype(dtype), ctx=mx.tpu(), dtype=dtype)

    def put(arr, dtype="int32"):
        nd = on_device(arr, dtype)
        return parallel.shard_batch(nd, mesh) if mesh is not None else nd

    inputs = (row.tokens, row.position_ids, row.q_mask, row.kv_mask)
    data, label = tuple(put(a) for a in inputs), put(row.label, "float32")
    opt = mx.optimizer.Adam(
        learning_rate=train["learning_rate"],
        multi_precision=train["multi_precision"],
        lr_scheduler=mx.lr_scheduler.FactorScheduler(
            step=1 << 40, warmup_steps=train["warmup_steps"]))
    loss = gluon.loss.BlockDiffusionLoss(block_rows=train["loss_block_rows"])
    step = parallel.DataParallelStep(net, loss, opt, mesh=mesh,
                                     shard_optimizer=shard_optimizer)

    def check(**controls):
        positions = onp.sort(onp.stack(
            [rs.choice(sizes["seq_len"], train["check_positions_per_row"],
                       replace=False) for _ in range(global_batch)]), 1)
        # eager, on the chip (the default context is the host's CPU)
        with mx.tpu():
            logits = net(*(on_device(a) for a in inputs),
                         on_device(positions))
        chosen = onp.stack([layer.experts.last_expert.asnumpy()
                            for layer in net.layers])
        return compare(logits.asnumpy(), chosen, host_params(net), row,
                       positions, sizes, **controls)

    return {"net": net, "step": step, "check": check, "row": row,
            "run": lambda: step(data, label)}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def dense_mask(rows, length, block, drop_own_block=False):
    """Block diffusion's mask from its three clauses, for the queries
    ``rows`` (indices into the 2 x ``length`` row ``[clean ; noised]``)
    against all 2 x ``length`` keys: (len(rows), 2 length) bool.  With
    ``b(i) = (i mod length) // block`` and ``clean(i) = i < length``,
    query i sees key j iff j is clean and ``b(j) < b(i)`` (everyone reads
    the clean past), or both are clean and ``b(j) == b(i)`` (a clean token
    sees its own whole block), or both are noised and ``b(j) == b(i)`` (a
    noised token sees its own noised block, both ways).
    ``drop_own_block`` leaves the third clause out: the control."""
    import jax.numpy as jnp

    cols = jnp.arange(2 * length)
    clean_i, clean_j = (rows < length)[:, None], (cols < length)[None, :]
    b_i = ((rows % length) // block)[:, None]
    b_j = ((cols % length) // block)[None, :]
    seen = (clean_j & (b_j < b_i)) | (clean_j & clean_i & (b_j == b_i))
    if not drop_own_block:
        seen = seen | (~clean_j & ~clean_i & (b_j == b_i))
    return seen


def _layer_fn(sizes, rounded=None, drop_own_block=False):
    """One layer of one row, jitted: ``(x (2L, E), position ids (2L,),
    layer parameters[, follow]) -> (x, p)`` with ``p`` (2L, experts) the
    router's probabilities.  A token's experts are the 8 largest of ``p``;
    where ``follow`` (2L, k) is given the experts run on those sets
    instead (the gates stay the layer's own probabilities of them,
    renormalised over the set) — see the module's docstring.  ``rounded``:
    a function put on every activation a matrix product reads or writes
    (the lower-precision control rounds there; the reference itself has
    none)."""
    import jax
    import jax.numpy as jnp

    rnd = rounded or (lambda x: x)
    eps = sizes["rms_norm_eps"]
    q_heads, kv_heads = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"]
    d, theta = sizes["head_dim"], float(sizes["rope_theta"])
    length, block = sizes["seq_len"], sizes["train"]["block_length"]
    k_routes = sizes["num_experts_per_tok"]
    held_from, held_to = sizes["deployment"]["experts_held"]

    def rms(x, gamma):
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gamma

    def rotate(x, pos):                  # x (S, h, d), rotate-half, all d
        freq = theta ** (-jnp.arange(d // 2) * 2.0 / d)
        ang = pos[:, None, None] * freq
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                                x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)

    def attention(h, pos, p):
        s = h.shape[0]
        qkv = rnd(h @ p["attn_qkv_weight"].T)
        q = qkv[:, :q_heads * d].reshape(s, q_heads, d)
        k, v = (qkv[:, (q_heads + i * kv_heads) * d:
                    (q_heads + (i + 1) * kv_heads) * d].reshape(
            s, kv_heads, d) for i in (0, 1))
        q = rotate(rms(q, p["attn_q_norm_gamma"]), pos).transpose(1, 0, 2)
        k = rotate(rms(k, p["attn_k_norm_gamma"]), pos).transpose(1, 0, 2)
        v = v.transpose(1, 0, 2)
        k, v = (jnp.repeat(t, q_heads // kv_heads, axis=0) for t in (k, v))
        rows_a_block = min(QUERY_ROWS, s)

        def rows(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, rows_a_block, axis=1)
            scores = jnp.einsum("hqd,hkd->hqk", qb, k) / (d ** 0.5)
            seen = dense_mask(start + jnp.arange(rows_a_block), length,
                              block, drop_own_block)
            prob = jax.nn.softmax(
                jnp.where(seen[None], scores, -1e30), axis=-1)
            # a query that sees no key (only under the control) gives zero
            prob = jnp.where(seen.any(-1)[None, :, None], prob, 0.0)
            return jnp.einsum("hqk,hkd->hqd", prob, v)

        out = jax.lax.map(rows, jnp.arange(0, s, rows_a_block))
        out = rnd(out.transpose(0, 2, 1, 3).reshape(s, q_heads * d))
        return rnd(out @ p["attn_out_weight"].T)

    def experts(h, p, follow):
        prob = jax.nn.softmax(h @ p["router_weight"].T, axis=-1)
        chosen = jnp.argsort(-prob, axis=-1, stable=True)[:, :k_routes] \
            if follow is None else follow
        picked = jnp.take_along_axis(prob, chosen, axis=1)
        gates = picked / (picked.sum(-1, keepdims=True) + 1e-20)
        out = jnp.zeros_like(h)
        for e in range(held_from, held_to):
            i = e - held_from
            y = rnd(jax.nn.silu(h @ p["experts_gate_weight"][i])
                    * (h @ p["experts_up_weight"][i])) \
                @ p["experts_down_weight"][i]
            gate = jnp.where(chosen == e, gates, 0.0).sum(-1)
            out = out + gate[:, None] * y
        return rnd(out), prob

    def layer(x, pos, p, follow=None):
        x = rnd(x + attention(rnd(rms(x, p["attn_norm_gamma"])), pos, p))
        out, prob = experts(rnd(rms(x, p["ffn_norm_gamma"])), p, follow)
        return rnd(x + out), prob

    return jax.jit(layer)


def _layer_params(params, i):
    pre = "layer%d_" % i
    return {name[len(pre):]: value for name, value in params.items()
            if name.startswith(pre)}


def reference_hidden(params, row, sizes, follow=None, rounded=None,
                     drop_own_block=False):
    """The final normed hidden states of the NOISED half (B, L, E) and
    every layer's router probabilities (layers, B, 2L, experts), as numpy
    arrays.  ``row``: ``block_diffusion_row``'s arrays (its tokens and
    position ids are read; the mask is made here, from the clauses).
    ``follow`` (layers, B, 2L, k): the sets to run the experts on instead
    of the reference's own.  One layer's weights are on the device at a
    time, one row goes through at a time."""
    import jax
    import jax.numpy as jnp

    eps, length = sizes["rms_norm_eps"], sizes["seq_len"]
    with jax.default_matmul_precision("highest"):
        xs = [jnp.asarray(params["embed_weight"][r]) for r in row.tokens]
        probs = []
        fn = _layer_fn(sizes, rounded=rounded, drop_own_block=drop_own_block)
        for i in range(sizes["num_hidden_layers"]):
            lp = {name: jnp.asarray(value)
                  for name, value in _layer_params(params, i).items()}
            rows = []
            for b in range(len(xs)):
                sets = None if follow is None else \
                    jnp.asarray(follow[i][b], jnp.int32)
                xs[b], p = fn(xs[b], jnp.asarray(row.position_ids[b],
                                                 jnp.float32), lp, sets)
                rows.append(onp.asarray(p))
            probs.append(onp.stack(rows))
            del lp
        gamma = jnp.asarray(params["final_norm_gamma"])
        hidden = [x[length:] / jnp.sqrt(
            (x[length:] ** 2).mean(-1, keepdims=True) + eps) * gamma
            for x in xs]
        return onp.stack([onp.asarray(h) for h in hidden]), onp.stack(probs)


def reference_logits(params, hidden, positions):
    """Logits (B, P, V) of the untied head at ``positions`` (B, P) of the
    noised half."""
    import jax
    import jax.numpy as jnp

    picked = onp.take_along_axis(hidden, positions[:, :, None], axis=1)
    with jax.default_matmul_precision("highest"):
        return onp.asarray(jax.jit(lambda h, w: h @ w.T)(
            jnp.asarray(picked), jnp.asarray(params["head_weight"])))


def reference_forward(params, row, positions, sizes):
    """Plain float32 forward: the logits (B, P, V) over the rows of the
    vocabulary held here at ``positions`` (B, P) of the noised half of
    each row.  ``params`` maps the zoo's parameter names (without the
    model prefix) to float32 arrays."""
    hidden, _ = reference_hidden(params, row, sizes)
    return reference_logits(params, hidden, onp.asarray(positions))


def reference_loss_and_grads(params, row, sizes, follow=None):
    """The block-diffusion loss — per row ``(1 / L) sum over the masked
    positions i of (1 / t_b(i)) * -log p(x0_i | row)``, logits at the
    noised half, position i predicting token i; the mean over rows — and
    its gradient for every trained parameter, float32, through the same
    plain layers (recomputed in the backward: ``jax.checkpoint``).
    ``follow`` (layers, B, 2L, k), where given, are the sets to run the
    experts on instead of the reference's own."""
    import jax
    import jax.numpy as jnp

    eps, length = sizes["rms_norm_eps"], sizes["seq_len"]
    layer = jax.checkpoint(_layer_fn(sizes))
    state = ("experts_balance_bias", "experts_expert_load",
             "experts_rows_computed", "mask_tiles")
    trained = {k: v for k, v in params.items() if not k.endswith(state)}

    def row_loss(trained, tokens, pos, label, weight, chosen):
        x = trained["embed_weight"][tokens]
        for i in range(sizes["num_hidden_layers"]):
            x, _ = layer(x, pos, _layer_params(trained, i),
                         None if chosen is None else chosen[i])
        x = x[length:]
        h = x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
            * trained["final_norm_gamma"]
        logp = jax.nn.log_softmax(h @ trained["head_weight"].T, axis=-1)
        got = jnp.take_along_axis(logp, jnp.maximum(label, 0)[:, None],
                                  axis=1)[:, 0]
        return jnp.where(label >= 0, -got * weight, 0.0).sum() / length

    with jax.default_matmul_precision("highest"):
        grad_fn = jax.jit(jax.value_and_grad(row_loss))
        on_device = {k: jnp.asarray(v) for k, v in trained.items()}
        loss, grads = 0.0, None
        batch = len(row.tokens)
        for b in range(batch):
            chosen = None if follow is None \
                else jnp.asarray(follow[:, b], jnp.int32)
            value, g = grad_fn(
                on_device, jnp.asarray(row.tokens[b], jnp.int32),
                jnp.asarray(row.position_ids[b], jnp.float32),
                jnp.asarray(row.label[b, 0], jnp.int32),
                jnp.asarray(row.label[b, 1]), chosen)
            loss += float(value) / batch
            g = {k: onp.asarray(v) / batch for k, v in g.items()}
            grads = g if grads is None else \
                {k: grads[k] + g[k] for k in g}
    return loss, grads


# ---------------------------------------------------------------------------
# operations, from shapes
# ---------------------------------------------------------------------------

def live_pairs(sizes):
    """The (query, key) pairs block diffusion's mask leaves live in one
    ``[clean ; noised]`` row of 2 x ``seq_len`` tokens, a query head:
    with n blocks of B tokens, clean on clean ``B^2 n (n + 1) / 2`` (the
    past and the own block), noised on clean ``B^2 n (n - 1) / 2`` (the
    past alone), noised on noised ``n B^2`` (the own block) — about a
    quarter of the square."""
    block = sizes["train"]["block_length"]
    n = sizes["seq_len"] // block
    return block * block * (n * (n + 1) // 2 + n * (n - 1) // 2 + n)


def attention_flops(sizes):
    """The floating-point operations the three masked flash kernels
    execute ON LIVE PAIRS in one step of one row, all layers and query
    heads: a live pair costs ``4 D`` in the forward (scores and values),
    ``6 D`` in ``flash_masked_dq`` (scores again, dP, dq) and ``8 D`` in
    ``flash_masked_dkv`` (scores again, dP, dk, dv), ``18 D`` in all.
    Dead pairs are not counted, whatever of them a partial tile
    computes, so a share of the peak made from this cannot pass 100%."""
    return 18 * sizes["head_dim"] * live_pairs(sizes) \
        * sizes["num_attention_heads"] * sizes["num_hidden_layers"]


def moe_product_flops(sizes, routes):
    """The floating-point operations of the routed experts' products for
    ``routes`` routes to held experts (a step's, every layer's): ``2 D F``
    for each of the three products of a gated expert (gate, up, down),
    times 3 — forward, input gradient, weight gradient.  Left out: the
    recomputing backward's second forward, the slots a block holds beyond
    the routes, the router, the gathers and sums."""
    return 3 * 3 * 2 * sizes["hidden_size"] \
        * sizes["moe_intermediate_size"] * routes


def moe_combine_bytes(sizes, rows, routes):
    """The bytes one call of ``moe_combine`` has to move, for ``rows`` rows
    of ``[clean ; noised]`` tokens (N = 2 ``seq_len`` tokens a row) and
    ``routes`` routes to held experts in the layer: one bfloat16 row of
    ``D`` read for each of those routes and the (N, D) bfloat16 result
    written.  Left out: the pass that packs the slots' table, the reads of
    row 0 for the routes that have no slot here, the routes' indices and
    gates."""
    tokens = rows * 2 * sizes["seq_len"]
    return 2 * sizes["hidden_size"] * (routes + tokens)


def model_flops(sizes):
    """Floating-point operations one ROW (``seq_len`` clean tokens, so a
    2 x ``seq_len``-token row) needs, forward and backward, from the
    shapes alone: matrix multiplications only (2 per multiply-add), the
    backward pass twice the forward, no recomputation; the attention
    scores and values at the LIVE pairs of the mask only
    (``live_pairs``), so that a dead pair computed flatters nothing; the
    routed experts at the share of the routes that an even router sends
    to the experts held (held / all, ``num_experts_per_tok`` routes a
    token); the head over the rows of the vocabulary held, at the noised
    half only.  Left out: norms, rotary, softmax, gates, the sort and
    gathers round the experts."""
    e, tokens = sizes["hidden_size"], 2 * sizes["seq_len"]
    q_width = sizes["num_attention_heads"] * sizes["head_dim"]
    kv_width = sizes["num_key_value_heads"] * sizes["head_dim"]
    experts = sizes["published"]["num_experts"]
    held = sizes["num_experts"] / experts
    # multiply-adds a token of the row
    layer = e * (q_width + 2 * kv_width) + q_width * e + e * experts \
        + sizes["num_experts_per_tok"] * held * 3 * e \
        * sizes["moe_intermediate_size"]
    attention = 2 * sizes["head_dim"] * live_pairs(sizes) \
        * sizes["num_attention_heads"]
    head = sizes["seq_len"] * e * sizes["vocab_size"]
    return 3 * 2 * (sizes["num_hidden_layers"] * (tokens * layer + attention)
                    + head)
