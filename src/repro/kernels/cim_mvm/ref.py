"""Pure-jnp oracle of the NeuRRAM voltage-mode bit-serial CIM MVM.

This is a bit-accurate behavioral model of one MVM on a NeuRRAM core
(paper Methods, 'Implementation of MVM with multi-bit inputs and outputs'):

  input phase:  n-bit signed inputs are decomposed into (n-1) ternary pulse
                phases; phase k's settled output voltage is
                    V_j^k = V_read * (p_k @ (G+ - G-))_j / norm_j
                (the voltage-mode conductance normalization) and is sampled &
                integrated for 2^k cycles, so the integrated charge is
                    Q_j = V_read * (x_int @ Gd)_j / norm_j   (+ non-idealities)
  output phase: sign bit from comparator polarity; magnitude bits by counting
                charge-decrement steps of size v_decr until polarity flips
                (early-stopped at N_max = 2^(out_bits-1)-1 steps). Activation
                functions are fused into this conversion: ReLU skips negative
                conversions; tanh/sigmoid warp the counter schedule; stochastic
                activations add LFSR noise to the integrator and emit the
                comparator bit.

All of it is differentiable-free integer/analog simulation; training-time paths
use the smooth surrogates in repro/core instead.
"""
from __future__ import annotations

from typing import TYPE_CHECKING, NamedTuple, Optional

import jax
import jax.numpy as jnp

if TYPE_CHECKING:      # kernels import nothing from core at module level:
    from ...core.types import CIMConfig   # core.cim imports this module


class CIMOutput(NamedTuple):
    counts: jax.Array      # (B, C) int32 — signed ADC counts (or binary samples)
    q_analog: jax.Array    # (B, C) float32 — pre-ADC integrated charge (volts)


def pwl_tanh_counts(steps, n_max: int):
    """Piecewise-linear tanh counter schedule (paper Methods).

    The chip increments the output counter every decrement step up to 35, then
    every 2 steps to 40, every 3 to 43, every 4 beyond — producing a PWL
    approximation of tanh saturation. Generalized to arbitrary n_max by scaling
    the paper's 43/128 knee layout.
    """
    steps = steps.astype(jnp.float32)
    s = n_max / 47.0  # paper schedule defined for ~47 counts max @ N_max=128
    k0, k1, k2 = 35.0 * s, 40.0 * s, 43.0 * s
    st0, st1, st2 = k0, k0 + 2.0 * (k1 - k0), k0 + 2.0 * (k1 - k0) + 3.0 * (k2 - k1)
    out = jnp.where(
        steps <= st0, steps,
        jnp.where(
            steps <= st1, k0 + (steps - st0) / 2.0,
            jnp.where(steps <= st2, k1 + (steps - st1) / 3.0,
                      k2 + (steps - st2) / 4.0)))
    return jnp.minimum(jnp.floor(out), n_max)


def adc_convert(q, cfg: CIMConfig, v_decr, *, key=None):
    """Neuron output phase: charge -> signed counts with fused activation."""
    n_max = cfg.out_mag_levels
    sign = jnp.sign(q)
    # round-to-nearest: the comparator flips when the cumulative decrement
    # first exceeds |Q|, i.e. mid-LSB, equivalent to rounding
    steps = jnp.floor(jnp.abs(q) / v_decr + 0.5)

    if cfg.activation == "relu":
        # conversion skipped (count forced 0) when comparator says negative
        mag = jnp.minimum(steps, n_max) * (sign > 0)
        return (mag).astype(jnp.int32)
    if cfg.activation in ("tanh", "sigmoid"):
        mag = pwl_tanh_counts(jnp.minimum(steps, 4 * n_max), n_max)
        out = sign * mag
        if cfg.activation == "sigmoid":
            out = jnp.floor((out + n_max) / 2.0)  # shift to [0, n_max]
        return out.astype(jnp.int32)
    if cfg.activation == "stochastic":
        assert key is not None, "stochastic activation needs a PRNG key"
        from ...core.noise import lfsr_noise
        noise = lfsr_noise(key, q.shape, v_decr * n_max)
        return (q + noise > 0).astype(jnp.int32)
    # "none": plain signed quantization
    return (sign * jnp.minimum(steps, n_max)).astype(jnp.int32)


def cim_mvm_ref(
    x_int: jax.Array,            # (B, R) int32 signed, |x| <= 2^(in_bits-1)-1
    g_pos: jax.Array,            # (R, C) float32 uS
    g_neg: jax.Array,            # (R, C) float32 uS
    v_decr,                      # scalar or (C,) — ADC decrement step (volts)
    cfg: CIMConfig,
    *,
    key: Optional[jax.Array] = None,
    adc_offset: Optional[jax.Array] = None,   # (C,) volts, non-ideality (vii)
    bit_serial: bool = True,
) -> CIMOutput:
    """Oracle CIM MVM. bit_serial=True walks the actual per-bit pulse phases
    (needed when per-phase non-idealities are enabled); bit_serial=False uses
    the algebraic identity sum_k 2^k p_k = x_int (identical when the datapath
    is linear)."""
    ni = cfg.nonideal
    gd = g_pos - g_neg                       # (R, C)
    gtot_row = jnp.sum(g_pos + g_neg, axis=1)  # (R,) total conductance per input wire
    norm = jnp.sum(g_pos + g_neg, axis=0)      # (C,)

    def settle(pulses):
        """One pulse phase: settled output voltage on each column (volts)."""
        v_in = pulses.astype(jnp.float32) * cfg.v_read          # (B, R)
        if ni.ir_drop_alpha > 0.0:
            # (i)+(ii): driver/input-wire droop grows with the total current the
            # active rows must source — nonlinear in the input pattern.
            load = jnp.abs(pulses.astype(jnp.float32)) @ gtot_row  # (B,)
            droop = jnp.clip(1.0 - ni.ir_drop_alpha * load, 0.7, 1.0)
            v_in = v_in * droop[:, None]
        v_out = (v_in @ gd) / norm                                # (B, C)
        if ni.wire_r_alpha > 0.0:
            # (iii): crossbar wire resistance — output attenuation growing with
            # column current (proxy: column total conductance).
            v_out = v_out * (1.0 - ni.wire_r_alpha * norm / jnp.max(norm))
        return v_out

    if bit_serial:
        from ...core.quant import int_bit_planes
        planes = int_bit_planes(x_int, cfg.in_mag_bits)           # (K, B, R)
        weights = 2 ** jnp.arange(cfg.in_mag_bits - 1, -1, -1, dtype=jnp.float32)
        v_phases = jax.vmap(settle)(planes)                       # (K, B, C)
        q = jnp.einsum("k,kbc->bc", weights, v_phases)
        if ni.coupling_sigma > 0.0:
            assert key is not None
            key, sub = jax.random.split(key)
            n_active = jnp.sum(jnp.abs(planes), axis=(0, 2)).astype(jnp.float32)
            q = q + (ni.coupling_sigma * jnp.sqrt(n_active + 1.0))[:, None] \
                * jax.random.normal(sub, q.shape)
    else:
        q = settle(x_int)

    if adc_offset is not None:
        q = q + adc_offset[None, :]
    if ni.adc_offset_sigma > 0.0 and adc_offset is None:
        assert key is not None
        key, sub = jax.random.split(key)
        q = q + ni.adc_offset_sigma * jax.random.normal(sub, (q.shape[-1],))[None, :]

    counts = adc_convert(q, cfg, v_decr, key=key)
    return CIMOutput(counts, q)


def dequantize_output(counts, v_decr, norm, w_max, in_scale, cfg: CIMConfig):
    """De-normalization (paper: 'we pre-compute [norm] from the weight matrix
    and multiply it back to the digital outputs'): map ADC counts back to
    x @ W units."""
    c = counts.astype(jnp.float32)
    if cfg.activation in ("tanh", "sigmoid", "stochastic"):
        return c  # activation outputs are already in neuron units
    return c * v_decr * norm[None, :] * w_max * in_scale \
        / (cfg.v_read * cfg.device.g_max)
