"""Model-driven chip calibration (paper Fig. 3b, Extended Data Fig. 5).

The chip's MVM output dynamic range varies per layer and per model; the ADC
charge-decrement step v_decr (and any per-neuron offsets) must be calibrated so
the output distribution fills the ADC swing. The paper stresses that the
calibration inputs must come from *training-set* activations (test-set-like
distribution), not random data — Extended Data Fig. 5 shows random inputs give
a markedly different output distribution.

calibrate_layer runs the analog front half (no ADC) of the CIM MVM on a batch
of training activations and returns the operating point.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .types import CIMConfig
from ..kernels.cim_mvm.ref import cim_mvm_ref


class LayerCalibration(NamedTuple):
    v_decr: jax.Array       # scalar ADC decrement step (volts)
    adc_offset: jax.Array   # (C,) volts measured with zero input, to cancel


def calibrate_v_decr(q_samples, cfg: CIMConfig, coverage: float = 0.999,
                     axis=None):
    """Pick v_decr so `coverage` of |Q| falls inside the N_max counts
    (over `axis`; None pools every sample)."""
    qmax = jnp.quantile(jnp.abs(q_samples), coverage, axis=axis)
    return jnp.maximum(qmax, 1e-9) / cfg.out_mag_levels


def tile_partial_sums(x_int, g_pos, g_neg, tiles, cfg: CIMConfig,
                      direction: str = "fwd"):
    """Normalized analog partial sums that cores (tiles) of ONE extent
    produce on a batch — the distributions their ADC operating points must
    cover. Returns (T, B, out) for the T tiles, all gathered at once.

    The TNSA reads the same programmed cells in either direction, and the
    two directions see DIFFERENT distributions (different summed wire count
    and a different voltage-mode normalizer), so each direction calibrates
    on its own partial sums:

      'fwd' (SL->BL): inputs drive the tile's weight rows, outputs appear
            on its columns; normalizer = per-column sum of G+ + G-.
      'bwd' (BL->SL): inputs drive the tile's COLUMNS, outputs appear on
            its rows; normalizer = per-row sum of G+ + G-.

    x_int: (B, R) / (B, C) integer activations in the direction's input
    space (full-matrix coordinates; each tile's slice is taken here).
    """
    rows, cols = tiles[0].rows, tiles[0].cols
    if any((t.rows, t.cols) != (rows, cols) for t in tiles):
        raise ValueError("tile_partial_sums takes tiles of one extent")
    r0 = jnp.asarray([t.row0 for t in tiles], jnp.int32)
    c0 = jnp.asarray([t.col0 for t in tiles], jnp.int32)
    block = jax.vmap(lambda a, r, c: jax.lax.dynamic_slice(
        a, (r, c), (rows, cols)), in_axes=(None, 0, 0))
    gp, gn = block(g_pos, r0, c0), block(g_neg, r0, c0)
    gd = gp - gn
    xf = x_int.astype(jnp.float32)

    def take(offsets, n):
        # (T, B, n) input slices: slice each distinct offset once, then
        # index whole slabs (a per-element gather is slow on CPU)
        starts = sorted(set(offsets))
        slabs = jnp.stack([xf[:, s:s + n] for s in starts])
        return slabs[np.searchsorted(starts, offsets)]

    if direction == "fwd":
        return (take([t.row0 for t in tiles], rows) @ gd) * cfg.v_read \
            / jnp.sum(gp + gn, axis=1)[:, None, :]
    if direction == "bwd":
        return (take([t.col0 for t in tiles], cols)
                @ jnp.swapaxes(gd, 1, 2)) * cfg.v_read \
            / jnp.sum(gp + gn, axis=2)[:, None, :]
    raise ValueError(f"direction must be 'fwd' or 'bwd', got {direction!r}")


def measure_adc_offsets(key, n_cols: int, cfg: CIMConfig):
    """Neuron-testing mode: zero input through the neurons reveals per-neuron
    offsets, which the controller stores and cancels digitally."""
    ni = cfg.nonideal
    if ni.adc_offset_sigma <= 0.0:
        return jnp.zeros((n_cols,), jnp.float32)
    return ni.adc_offset_sigma * jax.random.normal(key, (n_cols,))


def calibrate_layer(key, x_int_cal, g_pos, g_neg, cfg: CIMConfig,
                    coverage: float = 0.999) -> LayerCalibration:
    """x_int_cal: (B_cal, R) integer activations from the *training set*."""
    k1, k2 = jax.random.split(key)
    offs = measure_adc_offsets(k1, g_pos.shape[1], cfg)
    # Analog-only pass (v_decr=1 placeholder; we only use q_analog),
    # with the true offsets present, so v_decr covers offset-shifted Q.
    out = cim_mvm_ref(x_int_cal, g_pos, g_neg, 1.0, cfg, key=k2,
                      adc_offset=offs, bit_serial=False)
    v_decr = calibrate_v_decr(out.q_analog, cfg, coverage)
    return LayerCalibration(v_decr=v_decr, adc_offset=offs)


def search_v_read(key, x_int_cal, g_pos, g_neg, cfg: CIMConfig,
                  candidates=(0.2, 0.3, 0.4, 0.5, 0.6)):
    """Grid-search the read voltage: larger V_read raises SNR but worsens
    IR-drop droop (non-linear). Score = correlation of the analog output with
    the ideal linear MVM on the calibration batch."""
    import dataclasses
    gd = g_pos - g_neg
    norm = jnp.sum(g_pos + g_neg, axis=0)
    ideal = (x_int_cal.astype(jnp.float32) @ gd) / norm
    best_v, best_score = cfg.v_read, -jnp.inf
    for v in candidates:
        c = dataclasses.replace(cfg, v_read=float(v))
        out = cim_mvm_ref(x_int_cal, g_pos, g_neg, 1.0, c, key=key,
                          bit_serial=False)
        q = out.q_analog / v
        score = -jnp.mean((q - ideal) ** 2)
        take = score > best_score
        best_v = jnp.where(take, v, best_v)
        best_score = jnp.maximum(score, best_score)
    return best_v
