"""Compiles-for-v5e checks: the Pallas kernels of the main path, at the
shapes the models really produce, handed to the TPU v5e compiler for a chip
that is DESCRIBED (``v5e:2x2`` topology), not attached.  Interpret mode
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
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops import pallas_attention as PA
from mxnet_tpu.ops import pallas_fused_norm as FN
from mxnet_tpu.ops import pallas_layernorm as LN


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
]


@pytest.mark.parametrize("case", _ATTENTION, ids=[c[0] for c in _ATTENTION])
def test_flash_attention_fwd_bwd_compiles_at_dispatcher_blocks(one_chip,
                                                               case):
    """Forward and backward at the blocks ``attention_dispatch`` itself
    picks for the shape — the pair the custom-vjp ops hand the kernels."""
    _, layout, B, H, S, D, dtype, causal, lens, seg = case
    dtype = jnp.dtype(dtype)
    plan = PA.attention_dispatch(S, S, D, dtype, on_tpu=True, census=False)
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
        masks["q_segments"] = masks["kv_segments"] = sds((B, S), jnp.int32)
    fwd_fn, bwd_fn = {
        "bhsd": (PA.pallas_flash_attention, PA.pallas_flash_attention_bwd),
        "bshd": (PA.pallas_flash_attention_bshd,
                 PA.pallas_flash_attention_bwd_bshd)}[layout]

    def fwd(q, k, v, masks):
        return fwd_fn(q, k, v, causal=bool(causal), return_lse=True,
                      **blocks, **masks)

    def bwd(q, k, v, out, lse, do, masks):
        return bwd_fn(q, k, v, out, lse, do, causal=bool(causal),
                      **blocks, **masks)

    _compile(fwd, qkv, qkv, qkv, masks)
    _compile(bwd, qkv, qkv, qkv, qkv, lse, qkv, masks)


def test_layernorm_fwd_bwd_compiles_at_bert_shape(one_chip):
    """Both LayerNorm kernels at BERT-base's (24*512, 768) activation."""
    N, C = 24 * 512, 768
    x = jax.ShapeDtypeStruct((N, C), jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((C,), jnp.bfloat16, sharding=one_chip)
    stat = jax.ShapeDtypeStruct((N, 1), jnp.float32, sharding=one_chip)
    block = LN._pick_block_rows(C, rows=N, quiet=True)
    _compile(lambda x, g, b: LN.pallas_layer_norm_fwd(
        x, g, b, 1e-5, block_rows=block), x, g, g)
    _compile(lambda x, g, mu, rs, ct: LN.pallas_layer_norm_bwd(
        x, g, mu, rs, ct, block_rows=block), x, g, stat, stat, x)


@pytest.mark.parametrize("rows,cols", [(128, 256 * 56 * 56),
                                       (128, 2048 * 7 * 7)],
                         ids=["stage1_256x56x56", "stage4_2048x7x7"])
def test_bn_epilogue_fwd_bwd_compiles_in_bf16(one_chip, rows, cols):
    """The fused BN+add+ReLU epilogue at the 2D shapes the NCHW ResNet-50
    bs=128 step collapses to (rows = N, cols = C*H*W), in bf16 — the
    backward's ReLU-mask compare must not run in bf16 on a v5e."""
    x = jax.ShapeDtypeStruct((rows, cols), jnp.bfloat16, sharding=one_chip)
    s = jax.ShapeDtypeStruct((1, cols), jnp.float32, sharding=one_chip)
    _compile(FN.pallas_epilogue_fwd, x, s, s, x)
    _compile(FN.pallas_epilogue_bwd, x, s, x, x)


def test_flash_attention_compiles_inside_a_dp4_sharded_program(topo,
                                                               monkeypatch):
    """A Mosaic kernel cannot be partitioned automatically: inside a jitted
    program over dp-sharded operands (the ``DataParallelStep`` layout on a
    four-chip host) the custom-vjp op has to wrap it per shard.  BERT-base
    attention shape, global batch 24 over four described chips, forward and
    backward through the public op."""
    import numpy as onp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu import context
    from mxnet_tpu.parallel.mesh import batch_sharded_over

    monkeypatch.setattr(context, "on_tpu", lambda *a: True)
    mesh = Mesh(onp.array(topo.devices), ("dp",))
    over_dp = NamedSharding(mesh, P("dp"))
    qkv = jax.ShapeDtypeStruct((24, 12, 512, 64), jnp.bfloat16,
                               sharding=over_dp)
    lens = jax.ShapeDtypeStruct((24,), jnp.int32, sharding=over_dp)

    def loss(q, k, v, kv_lens):
        out = PA.flash_attention(q, k, v, False, None, kv_lens)
        return out.astype(jnp.float32).sum()

    def program(q, k, v, kv_lens):
        # the scope spans the backward's trace too, as in the train step
        with batch_sharded_over(mesh):
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v, kv_lens)

    text = _compile(program, qkv, qkv, qkv, lens)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
