"""Jit'd wrapper for the noisy training matmul."""
from __future__ import annotations

import jax.numpy as jnp

from ..cim_mvm.ops import default_interpret
from .kernel import noisy_matmul_pallas


def noisy_matmul(x, w, sigma_frac, seed=0, *, block=(256, 256, 256),
                 interpret=None):
    """y = x @ (w + sigma_frac * max|w| * eps), eps drawn in-kernel."""
    if interpret is None:
        interpret = default_interpret()
    sigma_abs = sigma_frac * jnp.max(jnp.abs(w))
    bm, bk, bn = block
    return noisy_matmul_pallas(x, w, sigma_abs, seed,
                               bm=bm, bk=bk, bn=bn, interpret=interpret)
