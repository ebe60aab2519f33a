"""Pallas fixture: BlockSpec/grid/index-map inconsistencies and a VMEM
budget violation (clamp constant mirrors pallas_attention's)."""
import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_CLAMP = 12 * 1024 * 1024


def _k(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2


def bad_specs(x):
    return pl.pallas_call(
        _k,
        grid=(4, 4),
        in_specs=[
            pl.BlockSpec((1, 128, 128), lambda i: (i, 0, 0)),  # expect: pallas-index-map-arity
            pl.BlockSpec((1, 128), lambda i, j: (i, j, 0)),  # expect: pallas-block-rank
        ],
        out_specs=[
            pl.BlockSpec((1, 128, 128), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((4, 512, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),  # expect: pallas-dim-semantics
    )(x)


def bad_out_arity(x):
    return pl.pallas_call(  # expect: pallas-block-rank
        _k,
        grid=(4,),
        in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((4, 128), jnp.float32),
            jax.ShapeDtypeStruct((4, 1), jnp.float32),
        ],
    )(x)


def huge_vmem(x):
    block_q = 4096
    block_k = 4096
    return pl.pallas_call(  # expect: pallas-vmem-budget
        _k,
        grid=(8,),
        in_specs=[pl.BlockSpec((block_q, block_k), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_q, block_k), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((32768, 4096), jnp.bfloat16)],
        scratch_shapes=[pltpu.VMEM((block_q, 128), jnp.float32)],
    )(x)


def tidy(x):
    # clean: consistent specs, tiny working set
    return pl.pallas_call(
        _k,
        grid=(4,),
        in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((4, 128), jnp.float32)],
    )(x)


def squeezed_dims(x, rows, lanes):
    # clean: a None block dim is squeezed — one element a grid step, not
    # the stand-in for a dim the folder cannot read (128**5 elements here)
    return pl.pallas_call(
        _k,
        grid=(2, 8, 64),
        in_specs=[pl.BlockSpec((None, None, None, rows, lanes),
                               lambda b, g, c: (b, g, c, 0, 0))],
        out_specs=[pl.BlockSpec((None, None, None, rows, lanes),
                                lambda b, g, c: (b, g, c, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((2, 8, 64, 128, 512),
                                        jnp.float32)],
    )(x)
