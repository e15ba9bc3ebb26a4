"""Continuous-batching scheduler (launch/scheduler): slot-pool invariants,
chunked-prefill continuity, one-trace decode, and the serving correctness
contract — a request served through the slotted pool is BITWISE-equal
(packed CIM ADC-count path included) to the same request served alone
through the static path, for a dense, an MoE and a recurrent arch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.data import traffic_requests
from repro.distributed.sharding import pool_pspecs, spec_axes
from repro.launch.scheduler import (ContinuousBatchingEngine, Request,
                                    init_pool)
from repro.launch.steps import arch_serving


def _cfg(arch, cim=False):
    cfg = configs.get(arch, smoke=True).replace(dtype=jnp.float32)
    if cim:
        cfg = cfg.replace(cim_mode="packed", moe_dropless=True)
    return cfg


def _params(cfg, cim=False):
    sv = arch_serving(cfg)
    params = sv.init_params(jax.random.PRNGKey(0))
    if cim:
        params = sv.deploy_cim(jax.random.PRNGKey(7), params, mode="ideal",
                               mesh_shape={"model": 1})
    return params


def _mixed_requests(cfg, lens, gens, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        (lens[i],)).astype(np.int32),
                    max_new=gens[i]) for i in range(len(lens))]


def _serve_alone_jit(cfg, params, prompt, max_new, max_len, width):
    """The static path, jitted exactly like serve.py's: jit prefill + jit
    decode (the pool jits compile the same graphs — eager execution can
    legitimately differ by 1 ulp in fused elementwise chains).

    Matmul shapes follow the pool's: the prompt prefills at batch 1 (the
    pool prefills one slot at a time), then the request decodes as row 0
    of a `width`-row batch beside zero rows (the pool decodes all its
    slots). XLA CPU computes a 1-row matmul with a matrix-vector kernel
    whose rounding differs from the multi-row one, so mismatched shapes
    would differ by 1 ulp for a reason that has nothing to do with the
    pool."""
    sv = arch_serving(cfg)
    prefill = jax.jit(sv.prefill)
    decode = jax.jit(sv.decode_step)
    cache = sv.init_state(1, max_len)
    logits, cache = prefill(params, cache,
                            jnp.asarray(prompt[None], jnp.int32))
    # every cache leaf but the scalar fill length keeps the batch at axis 1
    cache = {k: a if a.ndim < 2 else jnp.pad(
        a, [(0, 0), (0, width - 1)] + [(0, 0)] * (a.ndim - 2))
        for k, a in cache.items()}
    logits = jnp.pad(logits, [(0, width - 1), (0, 0)])
    rows = [np.asarray(logits[0])]
    toks = [int(jnp.argmax(logits[0]))]
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for _ in range(max_new - 1):
        logits, cache = decode(params, cache, tok)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        rows.append(np.asarray(logits[0]))
        toks.append(int(tok[0, 0]))
    return toks, rows


# ------------------------------------------------------- traffic generator

def test_traffic_requests_deterministic():
    """Same key -> identical traffic; lengths are page multiples in range;
    pad mask matches lengths; arrivals nondecreasing."""
    a = traffic_requests(jax.random.PRNGKey(5), 16, 512, min_len=32,
                         max_len=96, page=32, rate=40.0)
    b = traffic_requests(jax.random.PRNGKey(5), 16, 512, min_len=32,
                         max_len=96, page=32, rate=40.0)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    c = traffic_requests(jax.random.PRNGKey(6), 16, 512, min_len=32,
                         max_len=96, page=32, rate=40.0)
    assert not np.array_equal(np.asarray(a.tokens), np.asarray(c.tokens))
    lens = np.asarray(a.lengths)
    assert lens.min() >= 32 and lens.max() <= 96
    assert (lens % 32 == 0).all()
    mask = np.asarray(a.mask)
    np.testing.assert_array_equal(mask.sum(1), lens)
    assert (np.asarray(a.tokens)[~mask] == 0).all()
    arr = np.asarray(a.arrivals)
    assert (np.diff(arr) >= 0).all() and (arr > 0).all()
    gen = np.asarray(a.gen)
    assert gen.min() >= 4 and gen.max() <= 16


# ------------------------------------------------------ slot-pool invariants

def test_slot_pool_no_double_assign_and_eviction_frees():
    """More requests than slots: every slot is live for at most one request
    at a time, eviction returns the slot to the free list, and every
    request completes with exactly max_new tokens."""
    cfg = _cfg("gemma2-9b")
    params = _params(cfg)
    reqs = _mixed_requests(cfg, [32, 64, 32, 32, 64], [4, 2, 5, 3, 1])
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=96)

    assignments = []
    orig = eng._admit

    def traced_admit(req):
        orig(req)
        slot = eng._jobs[-1].slot
        assert slot not in eng._live, "slot double-assigned while live"
        assignments.append((slot, req.rid))
    eng._admit = traced_admit

    eng.run(reqs, realtime=False)
    assert sorted(eng._free) == [0, 1] and not eng._live and not eng._jobs
    assert not np.asarray(eng.pool["active"]).any()
    assert len(assignments) == len(reqs)       # every request got a slot
    for r in reqs:
        assert len(r.tokens) == r.max_new
        assert r.t_done >= 0 and r.t_first >= 0


def test_admission_resets_slot_state():
    """Admission zeroes the new slot's sequence state + bookkeeping, so a
    reused slot can never leak the previous request's KV/recurrent state."""
    cfg = _cfg("rwkv6-7b")
    pool = init_pool(cfg, 2, 64)
    dirty = {k: jax.tree_util.tree_map(lambda a: a + 1, v)
             for k, v in pool.items()}
    dirty["active"] = jnp.ones((2,), bool)
    from repro.launch.scheduler import _reset_slot
    out = _reset_slot(dirty, 1)
    for k, a in out.items():
        a = np.asarray(a)
        if k in ("len", "active", "tok"):
            assert a[1].max() == 0 and a[0].min() >= 1
        else:
            assert (a[:, 1] == 0).all(), f"{k} slot not zeroed"
            assert (a[:, 0] != 0).any(), f"{k} other slot clobbered"


@pytest.mark.parametrize("arch", ["rwkv6-7b", "zamba2-7b"])
def test_recurrent_state_isolated_per_slot(arch):
    """Admitting + prefilling a second request must leave the first slot's
    recurrent S/h state (and dense hybrid KV) bit-identical."""
    cfg = _cfg(arch)
    params = _params(cfg)
    reqs = _mixed_requests(cfg, [32, 64], [4, 4])
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=96)
    eng._admit(reqs[0])
    while eng._jobs:                       # prefill request 0 fully
        eng._prefill_one_chunk(0.0)
    snap = {k: np.asarray(v) for k, v in eng.pool.items()
            if k not in ("active", "tok")}
    eng._admit(reqs[1])                    # reset + prefill slot 1
    while eng._jobs:
        eng._prefill_one_chunk(0.0)
    for k, a in snap.items():
        got = np.asarray(eng.pool[k])
        if k == "len":
            np.testing.assert_array_equal(got[0], a[0])
        else:
            np.testing.assert_array_equal(got[:, 0], a[:, 0],
                                          err_msg=f"slot-0 {k} perturbed")


# ------------------------------------------- one decode trace, ever

def test_one_decode_trace_across_occupancy_changes():
    """The decode jit compiles ONCE: occupancy (free-slot bitmap, per-slot
    lens) changes values inside the donated pool pytree, never its
    structure. Prefill compiles once per distinct chunk length."""
    cfg = _cfg("gemma2-9b")
    params = _params(cfg)
    # mixed lens + gens force many occupancy patterns; 48 leaves a
    # remainder chunk (16) so prefill compiles exactly two chunk shapes
    reqs = _mixed_requests(cfg, [32, 48, 32, 96, 32, 64], [3, 6, 2, 4, 5, 1])
    eng = ContinuousBatchingEngine(cfg, params, n_slots=3, max_len=128)
    eng.run(reqs, realtime=False)
    assert eng.decode_traces() == 1
    assert eng._prefill._cache_size() == 2    # chunk lens {32, 16}


# ------------------------------------------- the serving correctness contract

@pytest.mark.parametrize("arch", ["gemma2-9b", "deepseek-moe-16b",
                                  "rwkv6-7b"])
def test_pool_bitwise_equals_static_cim(arch):
    """A request served through the slotted pool — co-batched with other
    requests, prefilled in interleaved chunks — is bitwise-equal on the
    packed CIM path to the same request served alone through the static
    path: every logits row and every greedy token. Dense, MoE (dropless
    dispatch) and recurrent (chunk-32-aligned prompts) archs."""
    cfg = _cfg(arch, cim=True)
    params = _params(cfg, cim=True)
    max_len = 128
    reqs = _mixed_requests(cfg, [32, 64, 96, 32], [5, 3, 4, 6])
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_len=max_len,
                                   chunk=32, capture_logits=True)
    stats = eng.run(reqs, realtime=False)
    assert stats["decode_traces"] == 1
    for r in reqs:
        toks, rows = _serve_alone_jit(cfg, params, r.prompt, r.max_new,
                                      max_len, eng.n_slots)
        assert toks == r.tokens, f"rid {r.rid}: greedy tokens diverge"
        assert len(rows) == len(r.logits)
        for i, (a, b) in enumerate(zip(rows, r.logits)):
            np.testing.assert_array_equal(
                a, b, err_msg=f"rid {r.rid} token {i}: logits not bitwise")


def test_moe_pool_requires_dropless():
    """The engine forces dropless MoE dispatch: with finite capacity a
    token's output depends on which other tokens share the batch — the
    documented reason moe_dropless exists."""
    cfg = _cfg("deepseek-moe-16b")
    assert not cfg.moe_dropless
    eng = ContinuousBatchingEngine(cfg, _params(cfg), n_slots=2, max_len=64)
    assert eng.cfg.moe_dropless


# ------------------------------------------------------------ pool sharding

def test_pool_pspecs_shard_slot_dim_over_data():
    cfg = _cfg("zamba2-7b")
    pool = init_pool(cfg, 4, 64)
    specs = pool_pspecs(pool, data_axes=("data",))
    # compare through spec_axes: jax normalizes a 1-tuple entry ('data',)
    # to 'data', and both spell the same placement
    for k, s in specs.items():
        if k in ("len", "active", "tok"):
            assert len(s) == 1 and spec_axes(s[0]) == ("data",)
        else:
            assert spec_axes(s[1]) == ("data",), \
                f"{k}: slot dim not on data axis"
            assert all(x is None for i, x in enumerate(s) if i != 1), \
                f"{k}: pool leaves shard ONLY the slot dim"
