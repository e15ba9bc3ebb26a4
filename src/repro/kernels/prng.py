"""Portable counter-based PRNG for Pallas kernels.

pltpu.prng_random_bits has no CPU interpret-mode lowering, so kernels use this
pure-arithmetic stateless hash instead (murmur3 finalizer over element
coordinates). It lowers on both the Pallas TPU backend and the CPU interpreter,
and is deterministic in (seed, tile coords, element coords) — the software
analogue of the chip's spatially-uncorrelated XOR'd LFSR chains.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _mix(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def hash_bits(shape, *salts):
    """uint32 random bits of `shape` from integer salts (scalars/traced)."""
    rows = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, shape, len(shape) - 1)
    h = rows * jnp.uint32(0x9E3779B9) + cols * jnp.uint32(0x7F4A7C15)
    for i, s in enumerate(salts):
        h = h + jnp.asarray(s).astype(jnp.uint32) * jnp.uint32(0x6C62272E + 2 * i)
        h = _mix(h)
    return _mix(h)


def hash_uniform(shape, *salts):
    """Uniform in [0, 1) on a 2^-24 grid: the top 24 hash bits go through
    int32 to float32 (exact — f32 has a 24-bit significand). Mosaic has no
    uint32 -> float32 cast, so this form is what lets the stochastic
    kernel epilogue and the noisy matmul compile on TPU."""
    top = (hash_bits(shape, *salts) >> 8).astype(jnp.int32)
    return top.astype(jnp.float32) * (1.0 / 16777216.0)


def hash_normal(shape, *salts):
    """Standard normal via Box-Muller on two hashed uniforms."""
    u1 = hash_uniform(shape, *salts, 1)
    u2 = hash_uniform(shape, *salts, 2)
    u1 = jnp.maximum(u1, 1e-7)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * jnp.pi * u2)
