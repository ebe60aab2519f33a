"""Share of the attention kernels' tiles that a mask given as data leaves
to visit, in %: visited over all, summed over the step's masked
``flash_attention`` calls, forward and both backward kernels, at the last
step of the window.  The live PAIRS of a block-diffusion row are a quarter
of the square; what the kernels visit depends on their blocks (31.25% at
512 x 512, 37.5% at 1024 x 1024, half at 512 x 2048).

From the gauges ``attention.mask.tiles_visited`` /
``attention.mask.tiles_total`` that ``publish_mask_tiles`` sets from the
model's state — the count made in the step from the same per-tile summary
the kernels skip by; None where the program has no such count."""


def read(facts):
    try:
        from mxnet_tpu.gluon.contrib.nn import publish_mask_tiles
    except ImportError:
        return None
    visited, total = publish_mask_tiles()
    return 100.0 * visited / total if total else None
