"""Small cells for the benchmark's CPU tests: the harness's run at test
widths on the CPU (the look for a chip skipped), with the timed path
intact or broken underneath.

At test widths one flipped ADC count or input level weighs more than at
published widths, so the cells' limits do not carry over. The tests hold
runs to data/smoke-check.json instead: share_off_best 0.012, set from
readings of the calibration procedure on the CPU (seeds 4294967313,
2147483653 and 1-7): sound runs read at most 0.0035 (smoke-codeqwen) and
0.0104 (smoke-rwkv6); the control reads 0.0868 and 0.0347 on seed
4294967313, the seed the control test uses (over all nine seeds its
lowest readings are 0.0451 and 0.0139).

The cells commit another number, share_gap_over_1.0sd (bench/checks):
at published widths every sound run already reads about half of its
served tokens off the reference's best, so only the tail of the gaps
tells the control apart. At test widths sound runs and the control both
read it at 0 to 0.0035, so the control tests hold it to the test cells'
number; the faults read 0.41-0.98 and fail the committed limits too."""
import json
import os
import time

import jax
import jax.numpy as jnp

from bench import harness

DATA = os.path.join(os.path.dirname(__file__), "data")
STATE_KEYS = {"k", "v", "S", "x_tm", "x_cm"}


def _json(name):
    with open(os.path.join(DATA, name + ".json")) as f:
        return json.load(f)


def mix(name: str) -> dict:
    return _json(name)


def check() -> dict:
    """The limits the test cells are held to."""
    return _json("smoke-check")


def committed_check(config_name: str) -> dict:
    """The limits that the benchmark's cell of the same family commits
    (bench/checks/<config>.batch-decode.json)."""
    cell = {"smoke-codeqwen": "codeqwen15-7b-L2.batch-decode",
            "wide-codeqwen": "codeqwen15-7b-L2.batch-decode",
            "smoke-rwkv6": "rwkv6-7b-L2.batch-decode"}[config_name]
    return harness.Bench().check(cell)


def with_committed(config_name: str) -> dict:
    """The test cells' limits and the committed cell's, together."""
    return dict(check(), **committed_check(config_name))


def over_limit(numbers: dict, limits: dict) -> list:
    """The compared numbers that exceed their limits."""
    return [k for k, v in limits.items() if numbers[k] > v]


def run(config_name: str, seed: int, hook=None, mix: str = "smoke-mix",
        limits: dict = None, seconds: float = 1.0) -> dict:
    bench = harness.Bench()
    return harness.run_cell(
        bench, "smoke", seed, seconds, False, t_start=time.perf_counter(),
        hook=hook, config=_json(config_name), mix=_json(mix),
        check=limits or check(),
        metric_list=[m for m in bench.spec["end_to_end"]
                     if "workloads" not in m])


def _wrap_decode(engine, change):
    dec = engine._decode

    def step(params, pool):
        return change(dec, params, pool)
    step._cache_size = dec._cache_size
    engine._decode = step


def token_altered(engine):
    """Every decoded token is replaced by its successor id."""
    vocab = engine.cfg.vocab

    def change(dec, params, pool):
        logits, pool = dec(params, pool)
        tok = jnp.where(pool["active"][:, None], (pool["tok"] + 1) % vocab,
                        pool["tok"])
        return logits, dict(pool, tok=jax.device_put(
            tok, pool["tok"].sharding))
    _wrap_decode(engine, change)


def state_unchanged(engine):
    """The decode step returns the KV cache / recurrent state it got."""
    def change(dec, params, pool):
        keep = {k: jnp.copy(v) for k, v in pool.items() if k in STATE_KEYS}
        logits, pool = dec(params, pool)
        return logits, dict(pool, **keep)
    _wrap_decode(engine, change)


def half_batch(engine):
    """The decode step runs every other slot only; the others keep their
    state and token. (Every other, not the upper half: an open loop at
    moderate load seldom fills the upper half of its slots.)"""
    def change(dec, params, pool):
        active = pool["active"]
        half = jnp.arange(active.shape[0]) % 2 == 0
        logits, pool = dec(params, dict(pool, active=active & half))
        return logits, dict(pool, active=active)
    _wrap_decode(engine, change)


FAULTS = {"token_altered": token_altered, "state_unchanged": state_unchanged,
          "half_batch": half_batch}
