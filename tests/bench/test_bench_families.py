"""The benchmark's model families, one file each (bench/families/<family>.py,
found by a configuration's `family`): the two published configurations
read exactly what they read before the files existed, and a family with
another tree and other packed calls runs a cell with nothing under bench/
edited."""
import hashlib
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import families, harness, reference, weights

import _cpu_cells as cells

DATA = os.path.join(os.path.dirname(__file__), "data")


def _data(name):
    with open(os.path.join(DATA, name + ".json")) as f:
        return json.load(f)
EXPORTS = ("arch_kw", "weights", "layers", "dispatches", "row_flops",
           "request_flops")


def _config(path):
    with open(os.path.join(os.path.dirname(__file__), "..", "..",
                           path)) as f:
        return json.load(f)


@pytest.mark.parametrize("name,sizes", [
    ("codeqwen15-7b-L2", dict(n_layers=2, d_model=4096, d_ff=13440,
                              vocab=92416, n_heads=32, n_kv_heads=4,
                              head_dim=128, qkv_bias=True,
                              rope_theta=1e6, tie_embeddings=False)),
    ("rwkv6-7b-L2", dict(n_layers=2, d_model=4096, d_ff=14336, vocab=65536,
                         n_heads=64, n_kv_heads=64, tie_embeddings=False))])
def test_family_file_gives_the_program_its_sizes(name, sizes):
    c = _config(f"bench/configs/{name}.json")
    fam = families.family(c)
    assert all(callable(getattr(fam, n)) for n in EXPORTS)
    cfg = harness.arch_config(c, None)
    assert {k: getattr(cfg, k) for k in sizes} == sizes
    assert (cfg.cim_mode, cfg.cim_in_bits, cfg.cim_out_bits) == \
        ("packed", 4, 8)


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", ["smoke-codeqwen", "smoke-rwkv6"])
def test_weights_and_logits_equal_the_parents(name):
    """SHA-256 of the seeded weights (leaf paths and bytes) and of the
    reference's logits on three sequences, recorded on the CPU before the
    family files existed (data/parent-pins.json)."""
    pins = _data("parent-pins")
    want = pins["configs"][name]
    c = _data(name)
    wkey, dkey = harness.keys(pins["digest_seed"])
    params = weights.make(c, wkey)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == want["weights_sha256"]
    spec = pins["digest_seqs"]
    rng = np.random.default_rng(spec["rng_seed"])
    seqs = [rng.integers(0, c["vocab_size"], n).astype(np.int32)
            for n in spec["lengths"]]
    rows = [np.arange(len(s) - spec["last_rows"], len(s)) for s in seqs]
    assert _digest(reference.logits(c, params, dkey, seqs, rows)) == \
        want["logits_sha256"]


@pytest.mark.parametrize("fault", [None, "token_altered"])
def test_moe_family_runs_a_cell(fault, monkeypatch):
    """deepseek-moe-16b at the program's smoke widths (8 routed experts,
    top 2, one shared; data/families/moe.py): its reference routes and
    deploys as the program does, so a sound run is correct (every replayed
    token the reference's best, on the CPU), and altered tokens are not."""
    monkeypatch.setattr(families, "FAMILIES", os.path.join(DATA, "families"))
    c = _data("smoke-deepseek-moe")
    fam = families.family(c)
    # 4 attention, 3 per routed expert and 3 shared calls a layer
    assert len(fam.dispatches(c)) == (4 + 3 * 8 + 3) * 2
    out = cells.run("smoke-deepseek-moe", 4294967313,
                    hook=cells.FAULTS.get(fault))
    assert out["failed"] == 0
    assert out["check"]["replayed_tokens"]["value"] > 0
    if fault is None:
        assert out["correct"] is True, out["check"]
        assert out["check"]["share_off_best"]["value"] == 0.0
    else:
        assert out["correct"] is False, out["check"]


@pytest.mark.parametrize("module", ["weights", "reference", "flops",
                                    "metrics.step_mfu_pct"])
def test_yardstick_modules_do_not_import_the_harness(module):
    """The family lookup sits in bench/families, below the harness: the
    modules that weights, reference and FLOPs come from load without it."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    code = ("import importlib, sys; "
            f"importlib.import_module('bench.{module}'); "
            "assert 'bench.harness' not in sys.modules, "
            "sorted(m for m in sys.modules if m.startswith('bench'))")
    subprocess.run([sys.executable, "-c", code], cwd=root, check=True,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))
