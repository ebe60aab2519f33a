"""The comparison that decides ``correct`` where logits are compared.

The system computes in bfloat16 (8 bits of mantissa: a relative rounding
step of 2**-8 = 0.4% at every activation), the plain reference in float32
at ``highest`` matmul precision.  Over the 50 (ResNet-50) or 12 x 6
(BERT-base) roundings between input and logits the errors add like a
random walk: PR 22's scratch runs saw a largest error of 0.5-0.9% of the
largest logit, for both models.  An 8-bit float format has 3 bits of
mantissa (6% a rounding) and would land above 10%.  So the tolerance is
3% of the largest reference logit: three to six times what bf16 shows,
and well under what any lower precision than bf16 activations would
produce.  It is the largest ABSOLUTE error that is held to it, not a
mean, so one wrong row fails.
"""
import numpy as onp

TOLERANCE = 0.03      # of the largest |reference logit|


def logits_agree(got, want):
    """Compare the system's logits with the reference's.  Returns the
    verdict with the numbers it rests on."""
    got = onp.asarray(got, dtype="float32")
    want = onp.asarray(want, dtype="float32")
    if got.shape != want.shape:
        return {"ok": False, "why": "shape %r != %r"
                % (got.shape, want.shape)}
    scale = float(onp.abs(want).max())
    finite = bool(onp.isfinite(got).all())
    err = float(onp.abs(got - want).max()) if finite else float("inf")
    return {"ok": finite and scale > 0 and err <= TOLERANCE * scale,
            "max_err": err, "scale": scale, "tolerance": TOLERANCE}
