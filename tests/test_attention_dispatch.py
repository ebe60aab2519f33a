"""Dispatch heuristic + short-sequence flash kernel tests.

The dispatcher (``attention_dispatch``) picks short_seq / streaming /
dense_fallback per shape; the short-seq kernel is the single-pass
forward (no online-softmax streaming state) plus the no-scratch
single-block dqkv backward.  Numerics run in interpret mode on CPU —
the same kernels compile for a described TPU in ``test_chip_compile.py``.
"""
import numpy as onp
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops import pallas_attention as P


def _rand(shape, seed, dtype="float32"):
    x = onp.random.RandomState(seed).uniform(-1, 1, shape).astype("float32")
    return jnp.asarray(x, jnp.dtype(dtype))


def _dense_masked(q, k, v, kv_lens=None, q_seg=None, kv_seg=None,
                  causal=False):
    d = q.shape[-1]
    tq, tk = q.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * d ** -0.5
    mask = jnp.ones((q.shape[0], 1, tq, tk), bool)
    if kv_lens is not None:
        mask = mask & (jnp.arange(tk)[None, None, None, :]
                       < kv_lens[:, None, None, None])
    if q_seg is not None:
        mask = mask & (q_seg[:, None, :, None] == kv_seg[:, None, None, :])
    if causal:
        mask = mask & (jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :])
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(mask, axis=-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


# --- dispatch heuristic ----------------------------------------------------

def test_dispatch_dense_fallback_off_tpu():
    # this suite runs on CPU: the public op must route dense
    assert P.attention_dispatch(512, 512, 64)["kernel"] == "dense_fallback"


def _plan(kernel, block_q=None, block_k=None, layout=None, heads=None):
    return {"kernel": kernel, "block_q": block_q, "block_k": block_k,
            "layout": layout, "heads_per_block": heads}


# (S, D, heads of a (B, T, H, D) caller or None) -> the whole plan, bf16
# on the chip: lengths either side of every threshold, then the cells'
# shapes (BERT 512 x 64 x 12 heads, ZAYA1 8192 x 128) and the shapes the
# (B, T, H, D) layout hands back to the (B, H, T, D) kernels
_PLANS = [
    ((64, 64, None), _plan("dense_fallback")),       # tiny: dense wins
    ((256, 64, None), _plan("short_seq", 256, 256, "bhsd", 1)),
    ((384, 64, None), _plan("short_seq", 384, 384, "bhsd", 1)),
    ((512, 64, None), _plan("short_seq", 512, 512, "bhsd", 1)),
    ((1000, 64, None), _plan("short_seq", 512, 1024, "bhsd", 1)),
    ((4096, 64, None), _plan("streaming", 512, 2048, "bhsd", 1)),
    ((512, 64, 12), _plan("short_seq", 512, 512, "bshd_pair", 2)),
    ((8192, 128, None), _plan("streaming", 512, 2048, "bhsd", 1)),
    ((512, 128, 8), _plan("short_seq", 512, 512, "bshd", 1)),
    ((512, 64, 11), _plan("short_seq", 512, 512, "bhsd", 1)),   # odd heads
    ((512, 32, 12), _plan("short_seq", 512, 512, "bhsd", 1)),   # D=32
]


@pytest.mark.parametrize("shape,want", _PLANS,
                         ids=["S%d-D%d-H%s" % s for s, _ in _PLANS])
def test_dispatch_table_on_tpu(shape, want):
    s, d, heads = shape
    assert P.attention_dispatch(s, s, d, "bfloat16", on_tpu=True,
                                census=False, bshd_heads=heads) == want


@pytest.mark.parametrize("dt", ["float32", "bfloat16"])
@pytest.mark.parametrize("d", [32, 64, 128, 256])
@pytest.mark.parametrize("s", [128, 384, 512, 1024, 2048, 4096, 8192])
def test_dispatch_never_exceeds_vmem_clamp(s, d, dt):
    """``tune_attention_blocks`` is the only source of blocks: every
    plan's forward working set, and its backward's at the q block
    ``_bwd_block_q`` gives it, honour the VMEM clamp, and the
    single-pass kernel holds the whole K axis in its one block."""
    plan = P.attention_dispatch(s, s, d, dt, on_tpu=True, census=False)
    assert plan["kernel"] in ("short_seq", "streaming")
    bq, bk = plan["block_q"], plan["block_k"]
    assert (bq, bk) == P.tune_attention_blocks(s, s, d, dt)
    Dp = d + (-d) % 64
    itemsize = jnp.dtype(dt).itemsize
    assert P._blocks_fit(bq, bk, Dp, itemsize), plan
    assert P._fwd_vmem_bytes(bq, bk, Dp, itemsize) <= P._VMEM_CLAMP, plan
    bwd_q = P._bwd_block_q(bq, bk, Dp, itemsize)
    assert P._bwd_vmem_bytes(bwd_q, bk, Dp, itemsize) <= P._VMEM_CLAMP, \
        (plan, bwd_q)
    if plan["kernel"] == "short_seq":
        assert bk >= s


# --- short-seq kernel numerics --------------------------------------------

def _mask_operands(cfg, B, S, seed=99):
    kv_lens = q_seg = kv_seg = None
    causal = cfg == "causal"
    if cfg == "kv_lens":
        rs = onp.random.RandomState(seed)
        kv_lens = jnp.asarray(rs.randint(S // 3, S + 1, (B,)), jnp.int32)
    elif cfg == "segments":
        seg = onp.zeros((B, S), onp.int32)
        for b in range(B):
            seg[b, (S // 3) * (b + 1):] = 1
        q_seg = kv_seg = jnp.asarray(seg)
    return causal, kv_lens, q_seg, kv_seg


def _check_short_seq(S, cfg, dtype):
    B, H, D = 2, 2, 64
    q, k, v = (_rand((B, H, S, D), i, dtype) for i in range(3))
    do = _rand((B, H, S, D), 7, dtype)
    causal, kv_lens, q_seg, kv_seg = _mask_operands(cfg, B, S)
    bq, bk = P.tune_attention_blocks(S, S, D, dtype)
    assert bk >= S        # whole K axis: the single-pass kernel path
    kw = dict(causal=causal, kv_lens=kv_lens, q_segments=q_seg,
              kv_segments=kv_seg, interpret=True, block_q=bq, block_k=bk)
    out, lse = P.pallas_flash_attention(q, k, v, return_lse=True, **kw)
    dq, dk, dv = P.pallas_flash_attention_bwd(q, k, v, out, lse, do, **kw)
    _, vjp = jax.vjp(
        lambda a, b, c: _dense_masked(a, b, c, kv_lens=kv_lens,
                                      q_seg=q_seg, kv_seg=kv_seg,
                                      causal=causal), q, k, v)
    ref = _dense_masked(q, k, v, kv_lens=kv_lens, q_seg=q_seg,
                        kv_seg=kv_seg, causal=causal)
    rq, rk, rv = vjp(do)
    tol = 0.06 if dtype == "bfloat16" else 5e-5
    for name, got, want in (("out", out, ref), ("dq", dq, rq),
                            ("dk", dk, rk), ("dv", dv, rv)):
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        assert err < tol, (name, S, cfg, dtype, err)


def test_short_seq_kernel_numerics_fast():
    """Tier-1 representative of the sweep below: non-power-of-two S with
    kv_lens in fp32 (single-pass fwd + single-block dqkv bwd)."""
    _check_short_seq(384, "kv_lens", "float32")


@pytest.mark.slow
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("cfg", ["causal", "kv_lens", "segments"])
@pytest.mark.parametrize("S", [128, 384, 512])
def test_short_seq_kernel_numerics(S, cfg, dtype):
    _check_short_seq(S, cfg, dtype)


def test_single_pass_fwd_matches_streaming_fwd():
    """The single-pass kernel (block_k = whole axis) must agree with the
    streaming kernel (block_k < axis) bit-for-fp32-bit."""
    B, H, S, D = 2, 3, 256, 64
    q, k, v = (_rand((B, H, S, D), 20 + i) for i in range(3))
    o1, l1 = P.pallas_flash_attention(q, k, v, causal=True, return_lse=True,
                                      interpret=True, block_q=128,
                                      block_k=256)   # n_k=1: single-pass
    o2, l2 = P.pallas_flash_attention(q, k, v, causal=True, return_lse=True,
                                      interpret=True, block_q=128,
                                      block_k=128)   # n_k=2: streaming
    assert float(jnp.max(jnp.abs(o1 - o2))) < 2e-6
    assert float(jnp.max(jnp.abs(l1 - l2))) < 2e-5


def test_single_block_bwd_matches_fused_and_split():
    """n_q == n_k == 1 routes the no-scratch single-block dqkv kernel;
    it must match both the q-streaming fused kernel and the split
    kernels."""
    B, H, S, D = 2, 2, 128, 64
    q, k, v, do = (_rand((B, H, S, D), 30 + i) for i in range(4))
    kv_lens = jnp.asarray([128, 77], jnp.int32)
    kw = dict(causal=False, kv_lens=kv_lens, interpret=True)
    o, l = P.pallas_flash_attention(q, k, v, return_lse=True,
                                    block_q=128, block_k=128, **kw)
    g_single = P.pallas_flash_attention_bwd(q, k, v, o, l, do,
                                            block_q=128, block_k=128, **kw)
    g_fused = P.pallas_flash_attention_bwd(q, k, v, o, l, do,
                                           block_q=64, block_k=128, **kw)
    o2, l2 = P.pallas_flash_attention(q, k, v, return_lse=True,
                                      block_q=64, block_k=64, **kw)
    g_split = P.pallas_flash_attention_bwd(q, k, v, o2, l2, do,
                                           block_q=64, block_k=64, **kw)
    for a, b in zip(g_single, g_fused):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5
    for a, b in zip(g_single, g_split):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5


def test_full_block_predicate_with_kv_lens_matches_masked():
    """Satellite fix: blocks wholly inside min(kv_lens) take the
    mask-free fast path — results must be identical to the masked path
    (exercised with lens that leave interior blocks fully visible)."""
    B, H, S, D = 2, 2, 384, 32
    q, k, v = (_rand((B, H, S, D), 40 + i) for i in range(3))
    kv_lens = jnp.asarray([384, 300], jnp.int32)
    out = P.pallas_flash_attention(q, k, v, interpret=True, block_q=128,
                                   block_k=128, kv_lens=kv_lens)
    ref = _dense_masked(q, k, v, kv_lens=kv_lens)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5
    # and causal + lens combined (both predicates must hold at once)
    out_c = P.pallas_flash_attention(q, k, v, causal=True, interpret=True,
                                     block_q=128, block_k=128,
                                     kv_lens=kv_lens)
    ref_c = _dense_masked(q, k, v, kv_lens=kv_lens, causal=True)
    assert float(jnp.max(jnp.abs(out_c - ref_c))) < 2e-5
