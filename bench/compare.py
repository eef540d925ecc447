"""The comparison that decides ``correct``: every answer the window
served against the plain float32 reference of the same input.

Each request carries one of the run's pooled inputs, so the reference
runs once per pooled input and every completion is held against the
reference of the input it carried. The numbers compared:

* ``out_rms``: the root mean square of ``served - reference`` over every
  completion and every compared output element, in the output's own
  units;
* ``out_rms_over_int8``: ``out_rms`` over the same root mean square of
  a plain int8 computation of the reference (every conv and dense
  weight per output channel and every input per tensor, at its absmax
  over the calibration samples, rounded to 8 bits) for the same
  requests. The seed's weights set how far any int8 computation lies
  from float32: on the VAE ``out_rms`` swings threefold from seed to
  seed while the program tracks the plain int8 computation to within a
  few percent on every seed, so this ratio is steady and separates the
  program from the int4 control where ``out_rms`` barely does;
* ``missing``: requests due in the window that never completed;
* ``nonfinite``: completions with a NaN or infinity in any output,
  random draws (the VAE's ``sample``) included;
* ``dispatcher_errors``: the scheduler's thread died.

The widest single gap swings from seed to seed; it is printed
(``out_max``) but not compared. A configuration names the numbers it
compares, with their limits, under ``limits``; ``missing``,
``nonfinite`` and ``dispatcher_errors`` are always compared, at 0.
Every other number is printed and not compared.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from bench.reference import Quant

EXACT = ("missing", "nonfinite", "dispatcher_errors")
INT8_RATIO = "out_rms_over_int8"


def reference_outputs(ref, cfg: Dict, params, pool: Dict[str, np.ndarray],
                      block: int = 32, quant=None) -> Dict[str, np.ndarray]:
    """The reference over the whole pool, ``block`` inputs per call."""
    import jax
    fwd = jax.jit(lambda p, b: ref.forward(cfg, p, b, quant=quant))
    n = len(next(iter(pool.values())))
    outs: Dict[str, List[np.ndarray]] = {}
    for i in range(0, n, block):
        got = fwd(params, {k: v[i:i + block] for k, v in pool.items()})
        for k, v in got.items():
            outs.setdefault(k, []).append(np.asarray(v, np.float64))
    return {k: np.concatenate(v) for k, v in outs.items()}


def rounded_outputs(ref, cfg: Dict, params, pool: Dict[str, np.ndarray],
                    bits: int) -> Dict[str, np.ndarray]:
    """The reference over the pool with every conv and dense weight and
    input rounded to ``bits`` (inputs at their absmax over the
    configuration's calibration samples, the first of the pool)."""
    absmax: Dict[str, float] = {}
    ref.forward(cfg, params, {k: v[:cfg["calibration_samples"]]
                              for k, v in pool.items()}, record=absmax)
    return reference_outputs(ref, cfg, params, pool,
                             quant=Quant(bits, absmax))


def readings(cfg: Dict, served: List[Dict[str, np.ndarray]],
             pool_index: np.ndarray, ref_out: Dict[str, np.ndarray],
             n_missing: int, int8_out: Optional[Dict[str, np.ndarray]] = None
             ) -> Dict[str, float]:
    """(the numbers, the widest single gap) for the served answers
    ``served[j]`` of the requests that carried pooled inputs
    ``pool_index[j]``; with ``int8_out``, the plain int8 reference's
    outputs over the pool, also ``out_rms_over_int8``."""
    sq = sq8 = 0.0
    count = 0
    widest = 0.0
    nonfinite = 0
    for out, idx in zip(served, pool_index):
        if not all(np.all(np.isfinite(np.asarray(v))) for v in out.values()):
            nonfinite += 1
            continue
        for k in cfg["compared_outputs"]:
            got = np.asarray(out[k], np.float64)
            want = ref_out[k][idx]
            if got.shape != want.shape:
                raise ValueError(f"output {k!r} has shape {got.shape}, "
                                 f"the reference {want.shape}")
            gap = got - want
            sq += float(np.sum(gap * gap))
            count += gap.size
            widest = max(widest, float(np.max(np.abs(gap))))
            if int8_out is not None:
                gap8 = int8_out[k][idx] - want
                sq8 += float(np.sum(gap8 * gap8))
    numbers = {"out_rms": (sq / count) ** 0.5 if count else 0.0,
               "missing": float(n_missing), "nonfinite": float(nonfinite)}
    if int8_out is not None:
        numbers[INT8_RATIO] = (sq / sq8) ** 0.5 if sq8 > 0 else 0.0
    return numbers, widest


def verdict(cfg: Dict, numbers: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}) over the numbers compared:
    those the configuration's ``limits`` names, and the exact ones."""
    limits = cfg.get("limits", {})
    missing = [k for k in limits if k not in numbers]
    if missing:
        raise KeyError(f"limits name numbers never read: {missing}")
    table = {k: {"value": v, "limit": float(limits.get(k, 0.0))}
             for k, v in numbers.items() if k in limits or k in EXACT}
    ok = all(row["value"] <= row["limit"] for row in table.values())
    return ok, table
