"""Compiles-for-v5e checks: the Pallas kernels of the main path and the
ResNet residual unit's train step, at the shapes the models really produce,
handed to the TPU v5e compiler for a chip that is DESCRIBED (``v5e:2x2``
topology), not attached.  Interpret mode
cannot see what these see — a bf16 vector compare Mosaic has no lowering
for, a working set past the scoped-VMEM limit — and every case here costs a
second or two and no chip time.  Nothing runs: a compile that passes says
nothing about results or speed.

One file on purpose.  Only one process may load the TPU's library, and it
keeps it until it exits, so the topology is described inside a fixture (after
a test of this file has started — never at import, in a ``skipif`` or a
``parametrize``), in the test's own process, and no second file does the same
from another worker.
"""
import collections
import math
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops import pallas_attention as PA
from mxnet_tpu.ops import pallas_layernorm as LN
from mxnet_tpu.ops import pallas_moe as MOE
from mxnet_tpu.ops import pallas_ssd as SSD


@pytest.fixture(scope="module")
def topo():
    """The described v5e host, with JAX's persistent compilation cache off
    while this module runs: a compile for a described chip is written to
    the cache but cannot be read back without one (the next run would warn
    and compile again)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %r" % (e,))
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *specs):
    """Lower + compile ``fn`` for the described chip; returns the compiled
    program's text.  Raises what the chip's compiler would raise."""
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.fixture(scope="module")
def compiled(one_chip):
    """``compiled(case_id)`` -> the texts of the case's forward and
    backward programs, each compiled once for the module: the test that a
    case compiles and the test of its kernels' names read the same texts."""
    texts = {}

    def get(case_id):
        if case_id not in texts:
            texts[case_id] = [_compile(fn, *specs)
                              for fn, specs in _PROGRAMS[case_id](one_chip)]
        return texts[case_id]
    return get


def _kernel_names(text):
    """The names of the ``tpu_custom_call`` instructions of a compiled
    program, without ``%`` and the numbering XLA appends — what
    ``benchmark/reduce_trace.op_name`` makes of a trace event."""
    names = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            head = line.split(" = ", 1)[0].split()[-1].lstrip("%")
            names.append(re.sub(r"(\.\d+)+$", "", head))
    return names


# (id, layout, B, H, S, D, dtype, causal, kv_lens, segment ids)
_ATTENTION = [
    ("bert_s512_kvlens", "bhsd", 24, 12, 512, 64, "bfloat16", 0, 1, 0),
    ("s2048", "bhsd", 4, 8, 2048, 64, "bfloat16", 0, 0, 0),
    ("s4096", "bhsd", 2, 8, 4096, 64, "bfloat16", 0, 0, 0),
    ("s8192", "bhsd", 1, 8, 8192, 64, "bfloat16", 0, 0, 0),
    ("s4096_causal_kvlens_segments", "bhsd", 2, 8, 4096, 64, "bfloat16",
     1, 1, 1),
    ("s8192_d128_causal", "bhsd", 1, 8, 8192, 128, "bfloat16", 1, 0, 0),
    ("s4096_f32_segments", "bhsd", 2, 8, 4096, 64, "float32", 0, 0, 1),
    ("bshd_s512_kvlens", "bshd", 24, 12, 512, 64, "bfloat16", 0, 1, 0),
    ("bshd_s4096_causal", "bshd", 2, 8, 4096, 64, "bfloat16", 1, 0, 0),
    ("bshd_s4096_d128_causal", "bshd", 2, 4, 4096, 128, "bfloat16", 1, 0, 0),
    ("bshd_s512_d128_kvlens", "bshd", 24, 6, 512, 128, "bfloat16", 0, 1, 0),
]


def _attention_programs(case):
    """Forward and backward at the blocks ``attention_dispatch`` itself
    picks for the shape — the pair the custom-vjp ops hand the kernels."""
    _, layout, B, H, S, D, dtype, causal, lens, seg = case
    dtype = jnp.dtype(dtype)

    def programs(one_chip):
        plan = PA.attention_dispatch(S, S, D, dtype, on_tpu=True,
                                     census=False)
        assert plan["kernel"] == ("short_seq" if S <= 2048 else "streaming")
        blocks = dict(block_q=plan["block_q"], block_k=plan["block_k"])

        def sds(shape, dt=dtype):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        qkv = sds((B, H, S, D) if layout == "bhsd" else (B, S, H, D))
        lse = sds((B, H, S), jnp.float32)
        masks = {}
        if lens:
            masks["kv_lens"] = sds((B,), jnp.int32)
        if seg:
            masks["q_segments"] = masks["kv_segments"] = sds((B, S),
                                                             jnp.int32)
        fwd_fn, bwd_fn = {
            "bhsd": (PA.pallas_flash_attention,
                     PA.pallas_flash_attention_bwd),
            "bshd": (PA.pallas_flash_attention_bshd,
                     PA.pallas_flash_attention_bwd_bshd)}[layout]

        def fwd(q, k, v, masks):
            return fwd_fn(q, k, v, causal=bool(causal), return_lse=True,
                          **blocks, **masks)

        def bwd(q, k, v, out, lse, do, masks):
            return bwd_fn(q, k, v, out, lse, do, causal=bool(causal),
                          **blocks, **masks)

        return [(fwd, (qkv, qkv, qkv, masks)),
                (bwd, (qkv, qkv, qkv, qkv, lse, qkv, masks))]
    return programs


def _layernorm_programs(one_chip):
    """Both LayerNorm kernels at BERT-base's (24*512, 768) activation."""
    N, C = 24 * 512, 768
    x = jax.ShapeDtypeStruct((N, C), jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((C,), jnp.bfloat16, sharding=one_chip)
    stat = jax.ShapeDtypeStruct((N, 1), jnp.float32, sharding=one_chip)
    block = LN._pick_block_rows(C)
    return [(lambda x, g, b: LN.pallas_layer_norm_fwd(
                x, g, b, 1e-5, block_rows=block), (x, g, g)),
            (lambda x, g, mu, rs, ct: LN.pallas_layer_norm_bwd(
                x, g, mu, rs, ct, block_rows=block), (x, g, stat, stat, x))]


# (id, B, S, heads, head width, groups, state, chunk, dtype): the Nemotron
# cell's scan, the same at float32 (products at HIGHEST), and chunks of 256
_SCANS = [
    ("ssd_nemotron", 1, 8192, 64, 64, 8, 128, 128, "bfloat16"),
    ("ssd_nemotron_f32", 1, 8192, 64, 64, 8, 128, 128, "float32"),
    ("ssd_chunk256", 1, 8192, 64, 64, 8, 128, 256, "bfloat16"),
]


def _scan_programs(case):
    """The scan's forward kernel, and what its backward rule runs: the
    states-only pass and the backward kernel — at a shape ``ssd_dispatch``
    gives the kernels."""
    _, B, S, H, P, G, N, chunk, dtype = case
    dtype = jnp.dtype(dtype)

    def programs(one_chip):
        assert SSD.ssd_dispatch(S, chunk, H, P, G, N, dtype,
                                on_tpu=True) == "kernel"

        def sds(shape, dt=dtype):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        x, bc = sds((B, S, H, P)), sds((B, S, G, N))
        rows = sds((B, G, 2 * (H // G), S), jnp.float32)
        skip = sds((1, H * P), jnp.float32)

        def bwd(x, b, c, rows, skip, dy):
            states = SSD.pallas_ssd_states(x, b, rows, chunk)
            return SSD.pallas_ssd_bwd(x, b, c, rows, skip, states, dy, chunk)

        return [(lambda x, b, c, rows, skip: SSD.pallas_ssd_fwd(
                    x, b, c, rows, skip, chunk), (x, bc, bc, rows, skip)),
                (bwd, (x, bc, bc, rows, skip, x))]
    return programs


# (id, blocks, slots, held, K, M): the experts' block products of the SDAR
# cell (gate / up, down) and of the Nemotron cell (up — which the chip keeps
# with 2688 minor, so the kernels read its (8, 1856, 2688) view —, down)
_BLOCK_PRODUCTS = [
    ("moe_sdar_up", 32, 1024, 16, 2048, 768),
    ("moe_sdar_down", 32, 1024, 16, 768, 2048),
    ("moe_nemotron_up", 16, 768, 8, 2688, 1856),
    ("moe_nemotron_down", 16, 768, 8, 1856, 2688),
]


def _block_product_programs(case):
    """The product through its ``custom_vjp``: the forward kernel, and what
    the backward rule runs — ``d_rows`` and ``d_weights`` — at a shape
    ``moe_product_dispatch`` gives the kernels."""
    _, B, S, G, K, M = case

    def programs(one_chip):
        assert MOE.moe_product_dispatch(S, K, M, "bfloat16",
                                        on_tpu=True) == "kernel"

        def sds(shape, dt=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        x, w, dy = sds((B, S, K)), sds((G, K, M)), sds((B, S, M))
        owner = sds((B,), jnp.int32)

        def bwd(x, w, owner, dy):
            return jax.vjp(lambda x, w: MOE.block_products(x, w, owner),
                           x, w)[1](dy)

        return [(MOE.block_products, (x, w, owner)), (bwd, (x, w, owner, dy))]
    return programs


# (id, B, H, key-value heads, S, D, dtype): a mask given as data (q_mask,
# kv_mask)
_MASKED = [
    # the SDAR cell's call: a [clean ; noised] row of 2 x 4096, GQA 32 on 4
    ("masked_s8192_d128_gqa8", 1, 32, 4, 8192, 128, "bfloat16"),
    # float32 operands at the same blocks: the largest working set
    ("masked_s4096_d128_f32", 1, 8, 8, 4096, 128, "float32"),
    # one K block, q streamed, and one block in all: the mask on every tile
    ("masked_s1024_d128_gqa8", 1, 32, 4, 1024, 128, "bfloat16"),
    ("masked_s256_d64", 2, 4, 4, 256, 64, "bfloat16"),
    # three integers a query (a floor under the ranks): a sliding layer's
    # call of the Laguna cell, 72 query on 8 key-value heads; and one K
    # block with the floor, 48 on 8
    ("masked_s4096_d128_gqa9_floor", 1, 72, 8, 4096, 128, "bfloat16", 3),
    ("masked_s1024_d128_gqa6_floor", 1, 48, 8, 1024, 128, "bfloat16", 3),
]


def _masked_programs(case):
    """Forward and backward under ``q_mask`` / ``kv_mask`` at the blocks
    ``attention_dispatch`` plans for a masked call."""
    _, B, H, Hkv, S, D, dtype = case[:7]
    q_width = case[7] if len(case) > 7 else 2
    dtype = jnp.dtype(dtype)

    def programs(one_chip):
        plan = PA.attention_dispatch(S, S, D, dtype, on_tpu=True,
                                     census=False, masked=True)
        blocks = dict(block_q=plan["block_q"], block_k=plan["block_k"])

        def sds(shape, dt=dtype):
            return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

        q, kv = sds((B, H, S, D)), sds((B, Hkv, S, D))
        mask, q_mask = sds((B, S, 2), jnp.int32), sds((B, S, q_width),
                                                      jnp.int32)

        def fwd(q, k, v, qm, km):
            return PA.pallas_flash_attention(
                q, k, v, return_lse=True, q_mask=qm, kv_mask=km, **blocks)

        def bwd(q, k, v, out, lse, do, qm, km):
            return PA.pallas_flash_attention_bwd(
                q, k, v, out, lse, do, q_mask=qm, kv_mask=km, **blocks)

        return [(fwd, (q, kv, kv, q_mask, mask)),
                (bwd, (q, kv, kv, q, sds((B, H, S), jnp.float32), q, q_mask,
                       mask))]
    return programs


# case id -> one_chip -> [(function, specs)]: a forward and its backward
_PROGRAMS = {c[0]: _attention_programs(c) for c in _ATTENTION}
_PROGRAMS.update({c[0]: _masked_programs(c) for c in _MASKED})
_PROGRAMS["layernorm_bert"] = _layernorm_programs
_PROGRAMS.update({c[0]: _scan_programs(c) for c in _SCANS})
_PROGRAMS.update({c[0]: _block_product_programs(c) for c in _BLOCK_PRODUCTS})

# case id -> the kernels of its forward, of its backward: the dispatcher's
# variants and the two layouts each under its own stable name
_STREAM = (["flash_stream_fwd"], ["flash_dkv", "flash_dq"])
_KERNELS = {
    "bert_s512_kvlens": (["flash_short_fwd"], ["flash_dqkv_single"]),
    "s2048": (["flash_short_fwd"], ["flash_dqkv_fused"]),
    "s4096": _STREAM,
    "s8192": _STREAM,
    "s4096_causal_kvlens_segments": _STREAM,
    "s8192_d128_causal": _STREAM,
    "s4096_f32_segments": _STREAM,
    # two 64-wide heads a lane block: ONE backward kernel, as in BHSD
    "bshd_s512_kvlens": (["flash_bshd_cols_fwd"], ["flash_bshd_cols_dqkv"]),
    # 64-wide heads past one K block: not taken in this layout
    "bshd_s4096_causal": _STREAM,
    "bshd_s4096_d128_causal": (["flash_bshd_stream_fwd"],
                               ["flash_bshd_dkv", "flash_bshd_dq"]),
    "bshd_s512_d128_kvlens": (["flash_bshd_cols_fwd"],
                              ["flash_bshd_cols_dqkv"]),
    "layernorm_bert": (["layernorm_fwd"], ["layernorm_bwd"]),
}
_KERNELS.update({c[0]: (["ssd_fwd"], ["ssd_bwd", "ssd_states"])
                 for c in _SCANS})
# the backward rule's jax.vjp traces the forward again: the compiler drops
# the product nothing reads
_KERNELS.update({c[0]: (["moe_blocks_fwd"],
                        ["moe_blocks_dw", "moe_blocks_dx"])
                 for c in _BLOCK_PRODUCTS})
# a streamed K axis under a mask as data has kernels of its own names (the
# tile summary is scalar-prefetched); the one-block kernels take the mask
# as two more operands under the names they have
_KERNELS.update({
    "masked_s8192_d128_gqa8": (["flash_masked_fwd"],
                               ["flash_masked_dkv", "flash_masked_dq"]),
    "masked_s4096_d128_f32": (["flash_masked_fwd"],
                              ["flash_masked_dkv", "flash_masked_dq"]),
    "masked_s1024_d128_gqa8": (["flash_short_fwd"], ["flash_dqkv_fused"]),
    "masked_s256_d64": (["flash_short_fwd"], ["flash_dqkv_single"]),
    # the floor rides in the same kernels: no fourth set
    "masked_s4096_d128_gqa9_floor": (["flash_masked_fwd"],
                                     ["flash_masked_dkv", "flash_masked_dq"]),
    "masked_s1024_d128_gqa6_floor": (["flash_short_fwd"],
                                     ["flash_dqkv_fused"]),
})


@pytest.mark.parametrize("case_id", [c[0] for c in _ATTENTION])
def test_flash_attention_fwd_bwd_compiles_at_dispatcher_blocks(compiled,
                                                               case_id):
    compiled(case_id)


@pytest.mark.parametrize("case_id", [c[0] for c in _MASKED])
def test_flash_attention_compiles_under_a_mask_given_as_data(compiled,
                                                             case_id):
    compiled(case_id)


def test_layernorm_fwd_bwd_compiles_at_bert_shape(compiled):
    compiled("layernorm_bert")


@pytest.mark.parametrize("case_id", [c[0] for c in _SCANS])
def test_ssd_scan_kernels_compile_at_dispatched_shapes(compiled, case_id):
    compiled(case_id)


@pytest.mark.parametrize("case_id", [c[0] for c in _BLOCK_PRODUCTS])
def test_moe_block_products_compile_at_both_cells_shapes(compiled, case_id):
    compiled(case_id)


@pytest.mark.parametrize("case_id", list(_PROGRAMS))
def test_kernels_carry_their_stable_names(compiled, case_id):
    """Every ``tpu_custom_call`` of the compiled forward and backward is
    named by its ``pallas_call(name=...)`` — the name a device trace shows
    and ``breakdown.device_ops`` reports — never by the jaxpr name stack
    (``jvp__``, a block's scope) an unnamed kernel inherits."""
    fwd_text, bwd_text = compiled(case_id)
    assert (sorted(_kernel_names(fwd_text)),
            sorted(_kernel_names(bwd_text))) == _KERNELS[case_id]


@pytest.mark.parametrize("layout", ["bhsd", "bshd"])
def test_flash_attention_compiles_inside_a_dp4_sharded_program(
        topo, monkeypatch, layout):
    """A Mosaic kernel cannot be partitioned automatically: inside a jitted
    program over dp-sharded operands (the ``DataParallelStep`` layout on a
    four-chip host) the custom-vjp op has to wrap it per shard.  BERT-base
    attention shape, global batch 24 over four described chips, forward and
    backward through the public op of either layout — in (B, T, H, D) two
    64-wide heads a lane block, one kernel each way."""
    import numpy as onp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu import context
    from mxnet_tpu.parallel.mesh import batch_sharded_over

    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    mesh = Mesh(onp.array(topo.devices), ("dp",))
    over_dp = NamedSharding(mesh, P("dp"))
    op, shape, kernels = {
        "bhsd": (PA.flash_attention, (24, 12, 512, 64),
                 ["flash_dqkv_single", "flash_short_fwd"]),
        "bshd": (PA.flash_attention_bshd, (24, 512, 12, 64),
                 ["flash_bshd_cols_dqkv", "flash_bshd_cols_fwd"])}[layout]
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=over_dp)
    lens = jax.ShapeDtypeStruct((24,), jnp.int32, sharding=over_dp)

    def loss(q, k, v, kv_lens):
        out = op(q, k, v, False, None, kv_lens)
        return out.astype(jnp.float32).sum()

    def program(q, k, v, kv_lens):
        # the scope spans the backward's trace too, as in the train step
        with batch_sharded_over(mesh):
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v, kv_lens)

    text = _compile(program, qkv, qkv, qkv, lens)
    assert sorted(_kernel_names(text)) == kernels


def test_ssd_scan_compiles_inside_a_dp4_sharded_program(topo):
    """The scan's custom-vjp entry inside a jitted program over dp-sharded
    operands, four rows over four described chips, forward and backward:
    its three kernels a shard, under their names."""
    import numpy as onp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel.mesh import batch_sharded_over

    mesh = Mesh(onp.array(topo.devices), ("dp",))

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    B, S, H, Pd, G, N = 4, 1024, 16, 64, 2, 128
    x = sds((B, S, H, Pd), jnp.bfloat16, P("dp"))
    dt = sds((B, S, H), jnp.bfloat16, P("dp"))
    bc = sds((B, S, G, N), jnp.bfloat16, P("dp"))
    head = sds((H,), jnp.float32, P())

    def loss(x, dt, a_log, b, c, d_skip, dt_bias):
        return SSD.ssd_scan_kernels(x, dt, a_log, b, c, d_skip, dt_bias,
                                    128).astype(jnp.float32).sum()

    def program(*args):
        with batch_sharded_over(mesh):
            return jax.value_and_grad(loss, argnums=tuple(range(7)))(*args)

    text = _compile(program, x, dt, head, bc, bc, head, head)
    assert sorted(_kernel_names(text)) == ["ssd_bwd", "ssd_fwd",
                                           "ssd_states"]


def _instructions(text):
    """(opcode, elements of the result, the line) of every instruction of
    a compiled program's text that has an array result."""
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if m:
            elements = 1
            for d in filter(None, m.group(1).split(",")):
                elements *= int(d)
            yield m.group(2), elements, line.strip()


def test_bert_layer_train_step_holds_no_head_transposes(one_chip,
                                                        monkeypatch):
    """One BERT-base encoder layer (hidden 768, 12 heads of 64, FFN 3072)
    at the cell's 64 rows of 512 tokens, bf16 with padded rows, as a
    ``DataParallelStep`` under Adam, compiled for the described chip:
    attention is exactly one forward and ONE backward kernel in the
    projections' own layout, and round them the program holds no
    transpose or copy of a head-split activation, no pad of an operand
    and no slice but the three-way split of ``qkv`` — the forward's
    output goes into the backward kernel and the output projection as
    the kernel wrote it."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import context, gluon, parallel
    from mxnet_tpu import random as mx_random
    from mxnet_tpu.gluon.contrib.nn import TransformerEncoderCell

    monkeypatch.setattr(context, "on_tpu", lambda *a: True)

    class Layer(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.cell = TransformerEncoderCell(768, 3072, 12,
                                                   prefix="layer0_")

        def hybrid_forward(self, F, x, valid_length):
            return self.cell(x, None, valid_length)

    net = Layer()
    net.initialize(mx.init.Zero())
    # deferred shapes do not depend on the batch: 8 tokens complete them
    # (too short for a kernel: composed attention, here on the CPU)
    net(mx.nd.zeros((1, 8, 768)),
        mx.nd.array(onp.array([8], "int32"), dtype="int32"))
    net.cast("bfloat16")
    step = parallel.DataParallelStep(
        net, gluon.loss.L2Loss(), mx.optimizer.Adam(learning_rate=1e-4))

    def spec(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)

    state = jax.tree_util.tree_map(
        spec, [[p._data._data for p in step._params], step._opt_states])
    carries = [jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
               jax.ShapeDtypeStruct((len(step._trainable),), jnp.float32,
                                    sharding=one_chip),
               spec(mx_random.next_key())]
    x = jax.ShapeDtypeStruct((64, 512, 768), jnp.bfloat16,
                             sharding=one_chip)
    lens = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)
    text = step._build().lower(*state, *carries, (x, lens),
                               x).compile().as_text()
    assert collections.Counter(_kernel_names(text)) == {
        "flash_bshd_cols_fwd": 1, "flash_bshd_cols_dqkv": 1}
    activation = 64 * 512 * 768
    bad = []
    for opcode, elements, line in _instructions(text):
        head_split = "[64,12,512,64]" in line or "[64,512,12,64]" in line
        if opcode in ("copy", "transpose") and (
                head_split or (elements >= activation and "_attn/" in line)):
            bad.append(line[:240])
        if opcode == "pad" and elements >= activation:
            bad.append(line[:240])
        if opcode in ("slice", "dynamic-slice") and elements >= activation \
                and not re.search(r'op_name="[^"]*_attn/split"', line):
            bad.append(line[:240])
    assert not bad, bad


# the residual units of ResNet-50's first and last stage at batch 128:
# (channels, height = width)
_RESIDUAL_UNITS = {"stage1_256x56x56": (256, 56),
                   "stage4_2048x7x7": (2048, 7)}


def _residual_units_step_text(one_chip, channels, hw, batch=128):
    """The compiled ``DataParallelStep`` program (forward, backward, SGD
    momentum) of two ``BottleneckV1`` units under a pooled classifier, in
    bf16 — the first unit's tail feeds a convolution and a skip, as in the
    zoo's nets.  Built and stepped once at a toy size for the optimizer
    state, then lowered at the real shapes for the described chip: the
    program never asks where it runs, so nothing is steered."""
    import numpy as onp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.model_zoo.vision.resnet import BottleneckV1

    net = nn.HybridSequential()
    net.add(BottleneckV1(channels, 1, False, in_channels=channels),
            BottleneckV1(channels, 1, False, in_channels=channels),
            nn.GlobalAvgPool2D(), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    x = mx.nd.array(onp.zeros((2, channels, 4, 4), "float32")) \
        .astype("bfloat16")
    y = mx.nd.array(onp.zeros((2,), "float32"))
    net(x)
    step = parallel.DataParallelStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.SGD(learning_rate=0.1, momentum=0.9, wd=1e-4))
    step(x, y)
    state = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip),
        [[p._data._data for p in step._params], step._opt_states,
         step._t_dev, step._lrs_dev, step._rng_dev])
    data = jax.ShapeDtypeStruct((batch, channels, hw, hw), jnp.bfloat16,
                                sharding=one_chip)
    label = jax.ShapeDtypeStruct((batch,), jnp.float32, sharding=one_chip)
    tails = [unit.body[-1].name for unit in list(net)[:2]]
    return step._build().lower(*state, data, label).compile().as_text(), \
        tails


@pytest.mark.parametrize("case_id", list(_RESIDUAL_UNITS))
def test_residual_tail_fuses_in_the_convolutions_layout(one_chip, case_id):
    """The BN+add+ReLU tail is plain ``jax.numpy``: the compiled train step
    holds no kernel, and no ``copy`` or ``transpose`` of a whole activation
    tensor is charged to a tail block (its ``op_name`` carries the block's
    name) — the Pallas epilogue it replaced was fed and followed by one at
    every call (13 in this program at stage 1)."""
    channels, hw = _RESIDUAL_UNITS[case_id]
    text, tails = _residual_units_step_text(one_chip, channels, hw)
    assert "tpu_custom_call" not in text
    whole = 128 * channels * hw * hw
    seen = set()
    layout_changes = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if not (m and op_name):
            continue
        seen.update(t for t in tails if "/%s/" % t in op_name.group(1))
        elements = 1
        for d in filter(None, m.group(2).split(",")):
            elements *= int(d)
        if m.group(3) in ("copy", "transpose") and elements >= whole \
                and any("/%s/" % t in op_name.group(1) for t in tails):
            layout_changes.append(line.strip()[:200])
    assert seen == set(tails), (seen, tails)    # the names are in the text
    assert not layout_changes, layout_changes


def test_zaya1_layer_train_step_compiles_with_named_kernels(one_chip,
                                                            monkeypatch):
    """One ZAYA1 layer at the published widths (hidden 2048, 8 query on 2
    key-value heads of 128, 8 of 16 experts of width 2048 held) under a
    small tied head, 2 rows of 8,192 tokens, bf16 with
    ``Adam(multi_precision=True)``: the ``DataParallelStep`` program
    compiles for the described chip, attention goes through the streamed
    forward and the split backward (causal, S=8192, D=128, the query heads
    sharing their key-value head in the index maps), and the grouped
    expert products are the compiler's own ragged-dot kernels — every
    ``tpu_custom_call`` under a stable name."""
    import mxnet_tpu as mx
    from mxnet_tpu import context, gluon, parallel
    from mxnet_tpu import random as mx_random

    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    net = gluon.model_zoo.zaya1(num_layers=1, vocab_size=8196,
                                experts_held=(0, 8))
    net.initialize(mx.init.Zero())
    net.cast("bfloat16")
    step = parallel.DataParallelStep(
        net, gluon.loss.TiedSoftmaxCrossEntropyLoss(block_rows=8196),
        mx.optimizer.Adam(learning_rate=1e-4, multi_precision=True))

    def spec(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)

    state = jax.tree_util.tree_map(
        spec, [[p._data._data for p in step._params], step._opt_states])
    carries = [jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
               jax.ShapeDtypeStruct((len(step._trainable),), jnp.float32,
                                    sharding=one_chip),
               spec(mx_random.next_key())]
    tokens = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)
    text = step._build().lower(*state, *carries, tokens,
                               tokens).compile().as_text()
    names = collections.Counter(_kernel_names(text))
    assert names == {"flash_stream_fwd": 1, "flash_dq": 1, "flash_dkv": 1,
                     "ragged-dot-none": 9, "ragged-dot-metadata": 2}
    # the blocks' names ride in the instructions' op_names
    for block in ("layer0_cca", "layer0_router", "layer0_experts",
                  "layer0_moe_norm"):
        assert "/%s%s/" % (net.prefix, block) in text, block


def _computations(text):
    """name -> body text of every computation of a compiled program."""
    comps = {}
    for m in re.finditer(r"^(?:ENTRY )?%([\w.\-]+) \(.*?^}", text,
                         re.S | re.M):
        comps["ENTRY" if m.group(0).startswith("ENTRY") else m.group(1)] = \
            m.group(0)
    return comps


def _reachable(comps, name):
    """The text of ``name`` and of every computation it calls (fusions,
    loop bodies), but not through a ``conditional``'s branches."""
    seen, todo = [], [name]
    while todo:
        body = comps[todo.pop()]
        seen.append(body)
        todo += [c for c in re.findall(r"(?:calls|body)=%([\w.\-]+)", body)
                 if c in comps]
    return "\n".join(seen)


def _route_sized(text, elements):
    """What makes an array of ``elements`` elements — the k N routes of an
    expert layer by its width — in a compiled step: the kind of every
    instruction with such a result (a fusion round a gather reads
    ``gather``, another fusion by its name), fusions' bodies, views
    (``bitcast``, ``parameter``, ``get-tuple-element``) and the ragged side
    of the experts' ``conditional``s (their first branch: it sorts all
    k N routes by design) left out."""
    comps = _computations(text)
    todo, seen, kinds = ["ENTRY"], set(), []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name].splitlines()[1:]:
            m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(",
                         line)
            if not m:
                continue
            head, result, opcode = m.groups()
            if opcode == "conditional":
                todo.append(re.findall(r"%([\w.\-]+)", line.split(
                    "branch_computations=")[1].split("}")[0])[1])
            todo += re.findall(r"(?:body|condition)=%([\w.\-]+)", line)
            sizes = [math.prod(int(d) for d in dims.split(",") if d)
                     for dims in re.findall(r"\w+\[([\d,]*)\]", result)]
            if elements not in sizes or opcode in (
                    "bitcast", "parameter", "get-tuple-element", "tuple"):
                continue
            if opcode == "fusion":
                body = comps[re.search(r"calls=%([\w.\-]+)", line).group(1)]
                opcode = "gather" if " gather(" in body \
                    else re.sub(r"(\.\d+)+$", "", head)
            kinds.append(opcode)
    return kinds


# compiled temporaries of the head alone (sandbox compile, described v5e,
# PR 29) plus 5%: under the row mean the compiler proves the cotangent
# uniform and drops the recomputing branch; under a loss scale it learns
# only when it runs, both branches are held
_HEAD_CASES = {"row_mean": (False, 1.209e9 * 1.05),
               "row_mean_times_a_runtime_scale": (True, 3.493e9 * 1.05)}


@pytest.mark.parametrize("case_id", list(_HEAD_CASES))
def test_zaya1_head_makes_its_gradients_in_one_loop_of_three_products(
        one_chip, case_id):
    """The tied head alone at the cell's shapes (2 x 8,192 x 2,048 against
    131,136 rows, bf16, ``block_rows=8196``) under ``value_and_grad``: the
    path taken is ONE ``while`` over 1,024-token blocks holding three
    products — logits, dh, dW into the carried float32 accumulator — and
    no second logits product; the recomputing loop exists only under a
    ``conditional`` (where the compiler cannot prove the cotangent one
    number), and the temporaries stay under the stated ceiling."""
    from mxnet_tpu.ops import nn as nn_ops

    runtime_scale, ceiling = _HEAD_CASES[case_id]
    rows, seq, units, vocab = 2, 8192, 2048, 131136

    def loss(h, w, lab, k):
        counted = (lab != -1).astype(jnp.float32)
        scale = counted / jnp.maximum(counted.sum(-1, keepdims=True), 1.0)
        row = jnp.sum(nn_ops.tied_softmax_cross_entropy(
            h, w, lab, scale=scale, block_rows=8196), axis=-1)
        return jnp.mean(row) * (k if runtime_scale else 1.0)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        spec((rows, seq, units), jnp.bfloat16),
        spec((vocab, units), jnp.bfloat16), spec((rows, seq), jnp.int32),
        spec((), jnp.float32)).compile()
    comps = _computations(compiled.as_text())
    taken = _reachable(comps, "ENTRY")
    assert len(re.findall(r" while\(", taken)) == 1
    blocks = "f32[%d,%d,%d]" % (rows, 512, vocab)    # 1,024 tokens' logits
    products = re.findall(r"= (\S+) convolution\(", taken)
    assert len(products) == 3, products
    assert sum(p.startswith(blocks) for p in products) == 1
    assert "f32[%d,%d]" % (rows * seq, 8196) not in taken
    branches = [b for line in re.findall(r" conditional\(.*", taken)
                for b in re.findall(r"%([\w.\-]+)", line.split(
                    "branch_computations=")[1].split("}")[0])]
    assert bool(branches) == runtime_scale
    if branches:
        fallback = "\n".join(_reachable(comps, b) for b in branches)
        assert len(re.findall(r" while\(", fallback)) == 1
        again = re.findall(r"= (\S+) convolution\(", fallback)
        assert sum(p.startswith("f32[%d,%d]" % (rows * seq, 8196))
                   for p in again) == 1, again
    assert compiled.memory_analysis().temp_size_in_bytes <= ceiling


# one layer of the Nemotron-H cell at the published widths: what a layer's
# compiled train step holds (kernel names) and may need (temporaries, GB)
_NEMOTRON_LAYERS = {
    # the scan is its three kernels (PR 31): the forward, and in the
    # backward the states-only pass and the backward kernel; the ceiling is
    # UNDER what the layer needed with the scan as plain XLA (1.370 GB at
    # the parent, its (Q, Q) decay blocks recomputed; 1.347 now)
    "M": ({"ssd_fwd": 1, "ssd_states": 1, "ssd_bwd": 1}, 1.360),
    # while the held experts' routes fit 16 blocks of 768 slots the experts
    # are the block products' kernels (PR 36): two forward, the same two
    # recomputed in the backward, two ``d_rows``, two ``d_weights``; the
    # side that runs when they do not fit holds the compiler's own
    # ragged-dot kernel: two grouped products forward, again in the
    # recomputed backward, four gradients.  The routes' buffers are
    # recomputed, not kept, the blocks side holds no (k N, D) array since
    # PR 34 and no gathered copy of a weight since PR 36: 1.727 GB and 5%
    # (1.736 with the copies, 2.073 before PR 34; 1.644 where the compiler
    # is let move the optimizer's float32 cast of the weights' gradients
    # into the ``conditional`` — which then hands out 0.32 GB a layer in
    # float32 to the step's end: four such layers need 2.548 GB with the
    # gradients handed out in bfloat16, 3.336 with the cast moved in)
    "E": ({"ragged-dot-none": 8, "ragged-dot-metadata": 3,
           "moe_blocks_fwd": 4, "moe_blocks_dx": 2, "moe_blocks_dw": 2},
          1.727 * 1.05),
}


def _float32_handed_out(text, held, k, m):
    """The float32 arrays of a weight's size, (held, k, m) or (held, m, k),
    among the results of a compiled step's ``conditional``s: the weights'
    gradients cast for the optimizer INSIDE the experts' ``cond`` and held
    in float32 from there to the step's end."""
    results = [line.split(" conditional(")[0]
               for line in text.splitlines() if " conditional(" in line]
    return [r for result in results for r in re.findall(
        r"f32\[%d,(?:%d,%d|%d,%d)\]" % (held, k, m, m, k), result)]


def _owners_copies(text, count, held, k, m):
    """What a compiled step holds of the gathered form of the experts'
    block products: every array of (count, k, m) or (count, m, k) — a copy
    of the (held, k, m) weight a block — and every ``scatter`` or
    ``dynamic-update-slice`` (the loops the compiler made of the
    ``scatter-add``) into an array of the weight's size: the copy's
    gradient un-made."""
    copies = re.findall(r"\w+\[%d,(?:%d,%d|%d,%d)\]" % (count, k, m, m, k),
                        text)
    scatters = [line.split(" = ", 1)[0].split()[-1]
                for line in text.splitlines()
                if re.search(r" (scatter|dynamic-update-slice)\(", line)
                and re.search(r"= \w+\[%d,(?:%d,%d|%d,%d)\]"
                              % (held, k, m, m, k), line)]
    return sorted(set(copies)), scatters


@pytest.mark.parametrize("kind", list(_NEMOTRON_LAYERS))
def test_nemotron_layer_train_step_compiles(one_chip, monkeypatch, kind):
    """One Mamba-2 layer and one expert layer (8 of 128 relu2 experts held,
    6 routes a token, the shared expert) of Nemotron-3-Nano at the published
    widths under a small untied head, 1 row of 8,192 tokens, bf16 with
    ``Adam(multi_precision=True)``: the ``DataParallelStep`` program
    compiles for the described chip, every ``tpu_custom_call`` under a
    stable name, the blocks' names in the instructions' ``op_name``s, the
    temporaries under the layer's ceiling.  The Mamba layer's scan is the
    three ``ssd_*`` kernels and nothing of the chunked form: no
    state-passing ``while``, no ``ssd.*`` scope, no float32 (Q, Q) decay
    or score block and no 5-D (…, G, R, P) array among the program's
    buffers.  The expert layer's blocks side moves its rows through
    ``ops.moe.dispatch`` and ``combine``: at most two instructions of the
    step make an array of k N x D = 49,152 x 2,688 elements, both gathers,
    none a ``select`` or a ``broadcast`` (the parent of PR 34 made seven:
    three gathers, a ``select_select_fusion``, a
    ``broadcast_multiply_fusion``, a ``broadcast`` and the ``conditional``
    that handed the routes' rows out).  Its block products read their
    owner's weight where it lies (PR 36): no array of 16 x 2,688 x 1,856
    elements — the parent held a gathered copy a product, 250 mentions in
    the text — and no ``scatter`` into a weight-sized array."""
    import mxnet_tpu as mx
    from mxnet_tpu import context, gluon, parallel
    from mxnet_tpu import random as mx_random

    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    net = gluon.model_zoo.nemotron_h(pattern=kind, vocab_size=2048,
                                     experts_held=(0, 8),
                                     bias_update_rate=1e-3)
    net.initialize(mx.init.Zero())
    net.cast("bfloat16")
    step = parallel.DataParallelStep(
        net, gluon.loss.TiedSoftmaxCrossEntropyLoss(block_rows=2048),
        mx.optimizer.Adam(learning_rate=1e-4, multi_precision=True))

    def spec(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)

    state = jax.tree_util.tree_map(
        spec, [[p._data._data for p in step._params], step._opt_states])
    carries = [jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
               jax.ShapeDtypeStruct((len(step._trainable),), jnp.float32,
                                    sharding=one_chip),
               spec(mx_random.next_key())]
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    compiled = step._build().lower(*state, *carries, tokens,
                                   tokens).compile()
    text = compiled.as_text()
    names, temp_gb = _NEMOTRON_LAYERS[kind]
    assert collections.Counter(_kernel_names(text)) == names
    blocks = {"M": ("layer0_mamba_in", "layer0_mamba_norm",
                    "layer0_mamba/ssd_fwd", "layer0_mamba/ssd_states",
                    "layer0_mamba/ssd_bwd"),
              "E": ("layer0_router", "layer0_experts", "layer0_shared_fc1")}
    for block in blocks[kind]:
        assert re.search(r"[/_]%s/" % re.escape(block), text), block
    if kind == "M":
        assert " while(" not in text        # one block of head rows: no loop
        assert "ssd." not in text.replace("pallas_ssd.py", "")
        scan_arrays = re.findall(
            r"f32\[[\d,]*128,128\]|\w+\[1,8192,8,8,64\]|"
            r"\w+\[1,64,128,8,8,64\]", text)
        assert not scan_arrays, sorted(set(scan_arrays))
    else:
        routes = _route_sized(text, 6 * 8192 * 2688)
        assert len(routes) <= 2 and set(routes) <= {"gather"}, routes
        assert _owners_copies(text, 16, 8, 2688, 1856) == ([], [])
        assert _float32_handed_out(text, 8, 2688, 1856) == []
        assert ".remat" not in text
    temp = compiled.memory_analysis().temp_size_in_bytes / 1e9
    print("temporaries of the %s layer's step: %.3f GB" % (kind, temp))
    assert temp <= temp_gb, temp


def test_sdar_layer_train_step_compiles_to_the_masked_kernels(one_chip,
                                                              monkeypatch):
    """One layer of the SDAR cell at the published widths (32 query on 4
    key-value heads of 128 with q/k norm and rotary at position ids, 16 of
    128 gated-SiLU experts of width 768 held, 8 routes a token) under a
    small untied head, one [clean ; noised] row of 2 x 4,096 tokens, bf16
    with ``Adam(multi_precision=True)`` and the block-diffusion loss: the
    ``DataParallelStep`` program compiles for the described chip; its
    attention is EXACTLY the three masked kernels — no unmasked flash
    kernel, and no (8192, 8192) score or mask array anywhere outside
    them —; the experts are the block products' kernels (PR 36: three
    forward, three recomputed, three ``d_rows``, three ``d_weights``, no
    array of 32 x 2,048 x 768 elements and no ``scatter`` into a
    weight-sized one) and, on the side of the ``conditional`` that a lumpy
    router takes, the compiler's ragged products; the blocks' names are in
    the instructions' ``op_name``s.  On
    the blocks side at most two instructions make an array of k N x D =
    65,536 x 2,048 elements, both gathers, none a ``select`` or a
    ``broadcast`` (the parent of PR 34 made six: three gathers, a
    ``select_select_fusion``, a ``broadcast`` and one more fusion)."""
    import mxnet_tpu as mx
    from mxnet_tpu import context, gluon, parallel
    from mxnet_tpu import random as mx_random

    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    net = gluon.model_zoo.sdar(num_layers=1, vocab_size=2048,
                               experts_held=(0, 16))
    net.initialize(mx.init.Zero())
    net.cast("bfloat16")
    step = parallel.DataParallelStep(
        net, gluon.loss.BlockDiffusionLoss(block_rows=2048),
        mx.optimizer.Adam(learning_rate=1e-4, multi_precision=True))

    def spec(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    state = jax.tree_util.tree_map(
        spec, [[p._data._data for p in step._params], step._opt_states])
    carries = [jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
               jax.ShapeDtypeStruct((len(step._trainable),), jnp.float32,
                                    sharding=one_chip),
               spec(mx_random.next_key())]
    data = (ints(1, 8192), ints(1, 8192), ints(1, 8192, 2),
            ints(1, 8192, 2))
    label = jax.ShapeDtypeStruct((1, 2, 4096), jnp.float32,
                                 sharding=one_chip)
    compiled = step._build().lower(*state, *carries, data, label).compile()
    text = compiled.as_text()
    names = collections.Counter(_kernel_names(text))
    assert {n: c for n, c in names.items() if n.startswith("flash")} == {
        "flash_masked_fwd": 1, "flash_masked_dq": 1, "flash_masked_dkv": 1}
    assert {n: c for n, c in names.items() if n.startswith("moe")} == {
        "moe_blocks_fwd": 6, "moe_blocks_dx": 3, "moe_blocks_dw": 3}
    assert {n for n in names if not n.startswith(("flash", "moe"))} \
        == {"ragged-dot-none", "ragged-dot-metadata"}
    assert _owners_copies(text, 32, 16, 2048, 768) == ([], [])
    assert _float32_handed_out(text, 16, 2048, 768) == []
    assert not re.findall(r"\w+\[(?:\d+,)*8192,8192\]", text)
    for block in ("layer0_attn_qkv", "layer0_attn/flash_masked_fwd",
                  "layer0_attn/flash_masked_dq",
                  "layer0_attn/flash_masked_dkv", "layer0_router",
                  "layer0_experts", "mask"):
        assert re.search(r"[/_]%s/" % re.escape(block), text), block
    routes = _route_sized(text, 8 * 8192 * 2048)
    assert len(routes) <= 2 and set(routes) <= {"gather"}, routes
    temp = compiled.memory_analysis().temp_size_in_bytes / 1e9
    print("temporaries of the SDAR layer's step: %.3f GB" % temp)
    assert ".remat" not in text
    assert temp <= 2.081 * 1.05, temp


# layer kind -> (the three lists' entry, the step's kernels by name, the
# ceiling on its temporaries in GB: 1.05 x the sandbox compile's)
_LAGUNA_LAYERS = {
    "sliding_sparse": (
        ("sliding_attention", 72, "sparse"),
        {"flash_masked_fwd": 1, "flash_masked_dq": 1, "flash_masked_dkv": 1,
         "moe_blocks_fwd": 6, "moe_blocks_dx": 3, "moe_blocks_dw": 3},
        1.847 * 1.05),
    "full_dense": (
        ("full_attention", 48, "dense"),
        {"flash_stream_fwd": 1, "flash_dq": 1, "flash_dkv": 1},
        0.779 * 1.05),
}


@pytest.mark.parametrize("kind", list(_LAGUNA_LAYERS))
def test_laguna_layer_train_step_compiles_to_the_kernels_expected(
        one_chip, monkeypatch, kind):
    """One sliding sparse layer (72 query on 8 key-value heads of 128, a
    gate a head, the default rotary, a window of 512; 8 of 256 gated-SiLU
    experts of width 1024 held, 10 routes a token, the shared expert) and
    one full dense layer (48 on 8, YaRN on 64 of 128 dimensions, the gated
    block of 12,288) of the Laguna cell at the published widths under a
    small untied head, one row of 4,096 tokens, bf16 with
    ``Adam(multi_precision=True)``: the ``DataParallelStep`` program
    compiles for the described chip; the window layer's attention is
    EXACTLY the three masked kernels — no (4096, 4096) score or mask
    array outside them —, the full layer's the three causal streamed
    ones; the experts are the block products' kernels with the compiler's
    ragged products on the ``conditional``'s other side; the blocks'
    names are in the instructions' ``op_name``s."""
    import mxnet_tpu as mx
    from mxnet_tpu import context, gluon, parallel
    from mxnet_tpu import random as mx_random

    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    (layer_type, heads, ffn), kernels, temp_gb = _LAGUNA_LAYERS[kind]
    net = gluon.model_zoo.laguna(
        num_layers=1, vocab_size=2048, experts_held=(0, 8),
        layer_types=[layer_type], heads_per_layer=[heads],
        mlp_layer_types=[ffn])
    net.initialize(mx.init.Zero())
    net.cast("bfloat16")
    step = parallel.DataParallelStep(
        net, gluon.loss.TiedSoftmaxCrossEntropyLoss(block_rows=2048),
        mx.optimizer.Adam(learning_rate=1e-4, multi_precision=True))

    def spec(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)

    state = jax.tree_util.tree_map(
        spec, [[p._data._data for p in step._params], step._opt_states])
    carries = [jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
               jax.ShapeDtypeStruct((len(step._trainable),), jnp.float32,
                                    sharding=one_chip),
               spec(mx_random.next_key())]
    tokens = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)
    compiled = step._build().lower(*state, *carries, tokens,
                                   tokens).compile()
    text = compiled.as_text()
    names = collections.Counter(_kernel_names(text))
    ragged = {n: c for n, c in names.items() if n.startswith("ragged-dot")}
    assert {n: c for n, c in names.items() if n not in ragged} == kernels
    assert bool(ragged) == (ffn == "sparse")
    assert not re.findall(r"\w+\[(?:\d+,)*4096,4096\]", text)
    blocks = ["layer0_attn_qkv", "layer0_attn_gate", "layer0_attn_out"]
    if ffn == "sparse":
        blocks += ["layer0_attn/flash_masked_fwd",
                   "layer0_attn/flash_masked_dq",
                   "layer0_attn/flash_masked_dkv", "layer0_router",
                   "layer0_experts", "layer0_shared_gate", "mask"]
        assert _owners_copies(text, 16, 8, 3072, 1024) == ([], [])
        assert _float32_handed_out(text, 8, 3072, 1024) == []
    else:
        blocks += ["layer0_attn/flash_stream_fwd", "layer0_attn/flash_dq",
                   "layer0_attn/flash_dkv", "layer0_ffn_gate",
                   "layer0_ffn_down"]
    for block in blocks:
        assert re.search(r"[/_]%s/" % re.escape(block), text), block
    assert ".remat" not in text
    temp = compiled.memory_analysis().temp_size_in_bytes / 1e9
    print("temporaries of the Laguna %s layer's step: %.3f GB" % (kind, temp))
    assert temp <= temp_gb, temp
