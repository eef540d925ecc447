"""Agreement rules between a served backend's outputs and the f32
reference — one definition for the cross-backend conformance tests and
the chip smoke run (``chip_smoke.py``).

* Integer outputs (argmax region classes) may flip only where the f32
  top-1/top-2 logit margin is inside what the model's pinned error bound
  can overcome (each logit moves at most ``atol``, so a margin above
  2x atol never flips), and only for a small fraction of rows.
* Float outputs of the int8 path stay within the model's pinned PTQ
  bound (:data:`ACCEL_ATOL`, measured on the conformance fixture and
  pinned with ~4x headroom).
* Thresholded decision outputs (ESPERTA's ``warn*``) are exempt from the
  value comparison: quantization legitimately moves near-threshold
  warnings.
"""
from __future__ import annotations

import numpy as np

# flex (XLA f32) vs the eager f32 reference on the same host: float
# associativity only
FLEX_TOL = dict(rtol=1e-5, atol=1e-5)
# per-model PTQ |output - reference| bounds, pinned with ~2-5x headroom
# over the max measured on the conformance fixture (jax 0.9.0 draws:
# baseline 6.8e-3, cnet 2.5e-2, esperta 1.1e-1, logistic 6.4e-3, reduced
# 2.5e-3, vae 2.0e-2). logistic's bound was 1e-5 while the older
# threefry draws made its head PTQ-demoted to f32; with jax's
# partitionable threefry default the head quantizes and carries int8
# error like the others.
ACCEL_ATOL = {
    "baseline_net": 0.05,
    "cnet_plus_scalar": 0.05,
    "multi_esperta": 0.3,
    "logistic_net": 0.03,
    "reduced_net": 0.02,
    "vae_encoder": 0.1,
}
DECISION_OF = {"region": "head"}       # argmax output -> its logit tensor


def is_decision(key: str) -> bool:
    return key.startswith("warn")


def assert_flips_margin_bounded(got, ref, logits_ref, atol, msg):
    """Argmax flips are only legitimate on near-ties: every flipped
    row's f32 top-1/top-2 margin must be within what the pinned logit
    perturbation can overcome, and flips must stay rare."""
    flipped = np.nonzero(got != ref)[0]
    assert flipped.size <= max(1, int(0.15 * got.size)), (
        f"{msg}: {flipped.size}/{got.size} decision flips")
    for i in flipped:
        top = np.sort(logits_ref[i].ravel())
        margin = float(top[-1] - top[-2])
        assert margin <= 2 * atol, (
            f"{msg}: row {i} flipped despite decisive f32 margin "
            f"{margin:.3e} > 2*atol={2*atol:.3e}")
