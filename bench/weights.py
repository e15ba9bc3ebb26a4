"""Seeded weights for a configuration file, made on the device in one jitted
call, in float32 (the type the packed CIM path serves), laid out as the
program's parameter tree. Each family's file (bench/families/) draws them.

The benchmark makes the weights, not the program: the plain reference
(bench/reference.py) regenerates the same arrays from the same seed and so
takes nothing that the system under test made.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .families import family


def normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def make(config: dict, key) -> Dict:
    """All weights of `config` from `key`, in one jitted call."""
    fam = family(config)
    return jax.jit(lambda k: fam.weights(k, config))(key)
