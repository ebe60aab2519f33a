"""ResNet-50 v1: the builders through the system's normal path, the plain
reference, and the FLOP count.

``build_train`` / ``build_serve`` are copies of ``chip_smoke``'s
``_build_vision_net`` and ``build_resnet_step`` (PR 21, proven on the chip)
with the seed and the sizes taken from the arguments: a later PR may edit
``chip_smoke.py``, none may move the yardstick.
"""
import numpy as onp


def _net(sizes):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.gluon.utils import materialize_params

    image = sizes["image_size"]
    net = vision.get_model(sizes["model"], classes=sizes["classes"])
    net.initialize(mx.init.Xavier())
    materialize_params(net, mx.nd.zeros((1, 3, image, image)))
    net.cast(sizes["dtype"])
    net.collect_params().reset_ctx(mx.tpu())
    return net


def _seed(seed):
    import mxnet_tpu as mx
    mx.random.seed(seed)
    onp.random.seed(seed)
    return onp.random.RandomState(seed)


def _params_in_graph_order(net):
    """float32 copies of the parameters in the order the net declares
    them (gluon's auto-numbered names differ from process to process; the
    graph order is the architecture's)."""
    return [p.data().asnumpy().astype("float32")
            for p in net.collect_params().values()]


def build_train(sizes, seed, global_batch, mesh=None, shard_optimizer=False):
    """Weights and the resident batch from ``seed``; returns a dict with
    the net, the ``DataParallelStep``, ``run()`` (one step on the resident
    batch, returns the loss NDArray) and ``check()`` (system logits and
    reference logits of a few rows, taken BEFORE the first step, in
    EVAL mode: in training mode batch statistics over a few rows carry
    bf16 rounding through 53 normalisations to 15% of the logit scale on
    the chip, PR 23, where float32 agrees to 2e-5 — no honest tolerance
    covers that, so the batch-statistics path is held by the loss
    falling, not by this check)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel

    rs = _seed(seed)
    image, train = sizes["image_size"], sizes["train"]
    net = _net(sizes)
    pixels = rs.uniform(size=(global_batch, 3, image, image)) \
        .astype("float32")
    classes = rs.randint(0, sizes["classes"], (global_batch,))

    def put(nd):
        return parallel.shard_batch(nd, mesh) if mesh is not None else nd

    data = put(mx.nd.array(pixels, ctx=mx.tpu()).astype(sizes["dtype"]))
    label = put(mx.nd.array(classes.astype("float32"), ctx=mx.tpu()))
    opt = mx.optimizer.SGD(learning_rate=train["learning_rate"],
                           momentum=train["momentum"], wd=train["wd"],
                           rescale_grad=1.0 / global_batch)
    step = parallel.DataParallelStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), opt, mesh=mesh,
        shard_optimizer=shard_optimizer)

    def check():
        rows = pixels[:min(8, global_batch)]
        params = _params_in_graph_order(net)
        with mx.tpu():
            got = net(mx.nd.array(rows, ctx=mx.tpu())
                      .astype(sizes["dtype"]))
        # the system saw the pixels rounded to its dtype: so does the
        # reference, which is about the arithmetic after that
        seen = rows.astype(sizes["dtype"]).astype("float32")
        want = reference_forward(params, seen, sizes, train=False)
        return got.asnumpy().astype("float32"), onp.asarray(want)

    return {"net": net, "step": step, "check": check,
            "run": lambda: step(data, label)}


def build_serve(sizes, seed, pool):
    """The eval-mode net for ``InferenceServer`` and a seeded pool of
    ``pool`` float32 images with the reference's logits for each."""
    rs = _seed(seed)
    image = sizes["image_size"]
    net = _net(sizes)
    images = rs.uniform(size=(pool, 3, image, image)).astype("float32")
    params = _params_in_graph_order(net)
    # the server rounds a request to its dtype: so does the reference
    seen = images.astype(sizes["dtype"]).astype("float32")
    want = onp.concatenate([
        onp.asarray(reference_forward(params, seen[i:i + 32], sizes,
                                      train=False))
        for i in range(0, pool, 32)])
    return {"net": net, "feature_shape": (3, image, image),
            "dtype": sizes["dtype"], "images": images, "reference": want}


def _units(sizes):
    """(channels out, stride, has a projection shortcut) of every residual
    unit, in order."""
    chans = sizes["stage_channels"]
    for stage, count in enumerate(sizes["units_per_stage"]):
        for unit in range(count):
            first = unit == 0
            yield (chans[stage + 1], 2 if first and stage > 0 else 1,
                   first and chans[stage + 1] != chans[stage])


def reference_forward(params, images, sizes, train):
    """Plain float32 ``jax.numpy`` forward of ResNet v1 from the paper and
    MXNet's zoo (7x7/2 stem, 3x3/2 max-pool, bottleneck 1x1-3x3-1x1 with
    the stride on the first 1x1, projection shortcut where the shape
    changes, BN after every convolution, global average pool, dense).
    ``train`` picks batch statistics (biased variance) over the running
    ones.  No kernels, ``highest`` precision.  ``params`` are the float32
    parameters in graph order: conv weight, then gamma, beta, running mean,
    running variance of its BN; a unit's body before its shortcut."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    eps = sizes["batchnorm_eps"]
    basic = sizes["bottleneck_ratio"] == 1      # rehearsal: ResNet-18

    def forward(params, x):
        it = iter(params)

        def conv(x, stride, pad):
            w = next(it)
            return lax.conv_general_dilated(
                x, w, (stride, stride), [(pad, pad), (pad, pad)],
                dimension_numbers=("NCHW", "OIHW", "NCHW"))

        def bn(x):
            gamma, beta, mean, var = next(it), next(it), next(it), next(it)
            if train:
                mean = x.mean((0, 2, 3))
                var = ((x - mean[None, :, None, None]) ** 2).mean((0, 2, 3))
            shape = (1, -1, 1, 1)
            return (x - mean.reshape(shape)) \
                / jnp.sqrt(var.reshape(shape) + eps) \
                * gamma.reshape(shape) + beta.reshape(shape)

        x = jax.nn.relu(bn(conv(x, 2, 3)))
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              [(0, 0), (0, 0), (1, 1), (1, 1)])
        for _, stride, project in _units(sizes):
            skip = x
            if basic:
                y = jax.nn.relu(bn(conv(x, stride, 1)))
                y = bn(conv(y, 1, 1))
            else:
                y = jax.nn.relu(bn(conv(x, stride, 0)))
                y = jax.nn.relu(bn(conv(y, 1, 1)))
                y = bn(conv(y, 1, 0))
            if project:
                skip = bn(conv(skip, stride, 0))
            x = jax.nn.relu(y + skip)
        x = x.mean((2, 3))
        w, b = next(it), next(it)
        assert next(it, None) is None, "parameters left over"
        return x @ w.T + b

    with jax.default_matmul_precision("highest"):
        return jax.jit(forward)([jnp.asarray(p) for p in params],
                                jnp.asarray(images, "float32"))


def conv_shapes(sizes):
    """(c_in, c_out, kernel, out_size) of every convolution, in order —
    the table ``model_flops`` adds up."""
    size = sizes["image_size"] // 2
    chans = sizes["stage_channels"]
    shapes = [(3, chans[0], 7, size)]
    size //= 2                                   # the max-pool
    c_in = chans[0]
    basic = sizes["bottleneck_ratio"] == 1
    for c_out, stride, project in _units(sizes):
        out = size // stride
        mid = c_out // sizes["bottleneck_ratio"]
        if basic:
            shapes += [(c_in, mid, 3, out), (mid, c_out, 3, out)]
        else:
            shapes += [(c_in, mid, 1, out), (mid, mid, 3, out),
                       (mid, c_out, 1, out)]
        if project:
            shapes.append((c_in, c_out, 1, out))
        c_in, size = c_out, out
    return shapes


def model_flops(sizes):
    """Floating-point operations one IMAGE needs, forward and backward,
    from the shapes alone: convolutions and the classifier only (2 per
    multiply-add), the backward pass twice the forward, no recomputation;
    BN, ReLU and pooling are not counted."""
    macs = sum(c_in * c_out * k * k * out * out
               for c_in, c_out, k, out in conv_shapes(sizes))
    macs += sizes["stage_channels"][-1] * sizes["classes"]
    return 3 * 2 * macs
