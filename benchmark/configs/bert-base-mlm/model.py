"""BERT-base masked-LM pre-training: the builder through the system's
normal path, the plain reference, and the FLOP count.

``build_train`` is a copy of ``chip_smoke.build_bert_step`` (PR 21, proven
on the chip) with the seed and the sizes taken from the arguments: a later
PR may edit ``chip_smoke.py``, none may move the yardstick.
"""
import numpy as onp


def _net(sizes):
    from mxnet_tpu.gluon.model_zoo import bert_base, bert_small

    common = dict(vocab_size=sizes["vocab_size"],
                  max_length=sizes["max_position_embeddings"], dropout=0.0,
                  use_pooler=False, use_decoder=True)
    if sizes["hidden_size"] == 768 and sizes["num_hidden_layers"] == 12:
        return bert_base(**common)
    # rehearsal only: the zoo's CI-sized model (4 heads)
    return bert_small(num_layers=sizes["num_hidden_layers"],
                      units=sizes["hidden_size"],
                      hidden_size=sizes["intermediate_size"], **common)


def build_train(sizes, seed, global_batch, mesh=None, shard_optimizer=False):
    """Weights and the resident batch from ``seed``; returns a dict with
    the net, the ``DataParallelStep``, ``run()`` (one step on the resident
    batch, returns the loss NDArray) and ``check()`` (system logits and
    reference logits of a few rows, taken BEFORE the first step)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel

    mx.random.seed(seed)
    onp.random.seed(seed)
    rs = onp.random.RandomState(seed)
    vocab, seq, batch = sizes["vocab_size"], sizes["seq_len"], global_batch
    net = _net(sizes)
    net.initialize(mx.init.Xavier())
    tokens = rs.randint(0, vocab, (batch, seq))
    lens = rs.randint(seq // 3, seq + 1, (batch,))
    lens[: max(1, batch // 4)] = seq
    n_pred = max(1, int(seq * sizes["masked_share"]))
    pos = onp.sort(onp.stack([rs.choice(int(lens.min()), n_pred,
                                        replace=False)
                              for _ in range(batch)]), 1)
    labels = rs.randint(0, vocab, (batch, n_pred))
    # deferred shapes do not depend on the batch: one row completes them
    net(mx.nd.array(tokens[:1].astype("float32")), None, None,
        mx.nd.array(lens[:1].astype("int32"), dtype="int32"),
        mx.nd.array(pos[:1].astype("int32"), dtype="int32"))
    net.cast(sizes["dtype"])
    net.collect_params().reset_ctx(mx.tpu())

    def on_device(arr, dtype):
        return mx.nd.array(arr.astype(dtype), ctx=mx.tpu(), dtype=dtype)

    def put(arr, dtype):
        nd = on_device(arr, dtype)
        return parallel.shard_batch(nd, mesh) if mesh is not None else nd

    data = (put(tokens, "float32"), None, None, put(lens, "int32"),
            put(pos, "int32"))
    label = put(labels, "float32")

    class MLMLoss(gluon.loss.Loss):
        def __init__(self):
            super().__init__(weight=None, batch_axis=0)
            self._ce = gluon.loss.SoftmaxCrossEntropyLoss()

        def hybrid_forward(self, F, outputs, lab):
            _, logits = outputs
            return self._ce(logits.reshape(-1, vocab), lab.reshape(-1))

    opt = mx.optimizer.Adam(learning_rate=sizes["train"]["learning_rate"])
    step = parallel.DataParallelStep(net, MLMLoss(), opt, mesh=mesh,
                                     shard_optimizer=shard_optimizer)

    def check():
        # one full row and three padded ones
        rows = sorted({0, batch // 4, batch // 2, batch - 1})
        params = {name[len(net.prefix):]:
                  p.data().asnumpy().astype("float32")
                  for name, p in net.collect_params().items()}
        # eager, so what the net makes itself (the position ids) has to
        # land on the chip too: the default context is the host's CPU
        with mx.tpu():
            _, logits = net(on_device(tokens[rows], "float32"), None, None,
                            on_device(lens[rows], "int32"),
                            on_device(pos[rows], "int32"))
        want = reference_forward(params, tokens[rows], lens[rows],
                                 pos[rows], sizes)
        return logits.asnumpy().astype("float32"), onp.asarray(want)

    return {"net": net, "step": step, "check": check,
            "run": lambda: step(data, label)}


def reference_forward(params, tokens, lens, positions, sizes):
    """Plain float32 ``jax.numpy`` forward of the same architecture, from
    the published description (post-LN encoder, exact GELU, fused-qkv rows
    ordered q|k|v, padding keys masked, the MLM head applied to the
    predicted positions only).  No kernels, ``highest`` matmul precision.
    ``params`` maps the zoo's parameter names (without the model prefix)
    to float32 arrays; weights are ``(out, in)`` as MXNet's Dense."""
    import jax
    import jax.numpy as jnp

    heads = sizes["num_attention_heads"]
    eps = sizes["layer_norm_eps"]

    def forward(params, tokens, lens, positions):
        def dense(x, name):
            return x @ params[name + "weight"].T + params[name + "bias"]

        def norm(x, name):
            mean = x.mean(-1, keepdims=True)
            var = ((x - mean) ** 2).mean(-1, keepdims=True)
            return (x - mean) / jnp.sqrt(var + eps) \
                * params[name + "gamma"] + params[name + "beta"]

        b, s = tokens.shape
        x = params["word_embed_weight"][tokens] \
            + params["pos_embed_weight"][jnp.arange(s)][None]
        x = norm(x, "embed_ln_")
        d = x.shape[-1] // heads
        key_ok = jnp.arange(s)[None, :] < lens[:, None]        # (B, S)
        for i in range(sizes["num_hidden_layers"]):
            pre = "encoder_layer%d_" % i
            q, k, v = (part.reshape(b, s, heads, d).transpose(0, 2, 1, 3)
                       for part in jnp.split(dense(x, pre + "attn_qkv_"),
                                             3, axis=-1))
            scores = q @ k.transpose(0, 1, 3, 2) / (d ** 0.5)
            scores = jnp.where(key_ok[:, None, None, :], scores, -1e30)
            att = jax.nn.softmax(scores, axis=-1) @ v
            att = att.transpose(0, 2, 1, 3).reshape(b, s, heads * d)
            x = norm(x + dense(att, pre + "attn_out_"), pre + "attn_ln_")
            h = jax.nn.gelu(dense(x, pre + "ffn_fc1_"), approximate=False)
            x = norm(x + dense(h, pre + "ffn_fc2_"), pre + "ffn_ln_")
        picked = jnp.take_along_axis(x, positions[:, :, None], axis=1)
        h = jax.nn.gelu(dense(picked, "decoder_fc_"), approximate=False)
        return dense(norm(h, "decoder_ln_"), "decoder_out_")

    with jax.default_matmul_precision("highest"):
        return jax.jit(forward)(
            {name: jnp.asarray(p) for name, p in params.items()},
            jnp.asarray(tokens, "int32"), jnp.asarray(lens, "int32"),
            jnp.asarray(positions, "int32"))


def model_flops(sizes):
    """Floating-point operations one ROW of ``seq_len`` tokens needs,
    forward and backward, from the shapes alone: matrix multiplications
    only (2 per multiply-add), the backward pass twice the forward, no
    recomputation, the padded length (padding waste is the packed cell's
    subject), embeddings as look-ups."""
    e, f, s = sizes["hidden_size"], sizes["intermediate_size"], \
        sizes["seq_len"]
    predicted = max(1, int(s * sizes["masked_share"]))
    layer = s * (3 * e * e + e * e + 2 * e * f) + 2 * s * s * e
    head = predicted * (e * e + e * sizes["vocab_size"])
    return 3 * 2 * (sizes["num_hidden_layers"] * layer + head)
