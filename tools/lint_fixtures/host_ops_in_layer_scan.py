# lint-expect: R003
# A layer-scan body handed to models/transformer.scan_layers is traced like
# any lax.scan body: host numpy and a Python `if` on the carry are bugs
# there too.
import jax.numpy as jnp
import numpy as np

import repro.models.transformer as T
from repro.models.transformer import scan_layers


def forward(params, x):
    def body(x, p):
        if x.sum() > 0:                         # BUG: `if` on tracer
            x = -x
        return x @ p["w"], None

    x, _ = scan_layers(body, x, params["layers"])
    return x


def prefix(params, x):
    def body(x, p):
        return x + np.tanh(p["b"]), None        # BUG: np under trace

    x, _ = T.scan_layers(body, x, params["layers"])
    return jnp.asarray(x)
