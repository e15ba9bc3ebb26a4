"""`correct` on sound runs, and the control (the reference at the next
precision down) failing the same limits, on the CPU: at test widths
against the test cells' limits, and at 512 wide against those together
with the limit that codeqwen's cell commits."""
import pytest

from bench import harness, traffic, weights

import _cpu_cells as cells


def _spy(monkeypatch):
    """Keeps what the harness replays through the reference."""
    replayed = {}
    gaps_fn = harness.token_gaps

    def spy(config, params, deploy_key, replay, *a, **kw):
        replayed.update(config=config, key=deploy_key, replay=replay)
        return gaps_fn(config, params, deploy_key, replay, *a, **kw)
    monkeypatch.setattr(harness, "token_gaps", spy)
    return replayed, gaps_fn


def _control(replayed, gaps_fn, seed):
    """The sound reading and the control's on the replayed tokens."""
    wkey, _ = harness.keys(seed)
    params = weights.make(replayed["config"], wkey)
    ref_gaps, ref = gaps_fn(replayed["config"], params, replayed["key"],
                            replayed["replay"])
    ctl_gaps, _ = gaps_fn(replayed["config"], params, replayed["key"],
                          replayed["replay"], precision="high", against=ref)
    return harness.gap_numbers(ref_gaps), harness.gap_numbers(ctl_gaps)


@pytest.mark.parametrize("name,mix,seed,limits", [
    ("smoke-codeqwen", "smoke-mix", 4294967313, "test"),
    ("smoke-rwkv6", "smoke-mix", 4294967313, "test"),
    # 512 wide, head 128, 8 requests replayed, seed 11: share_off_best
    # reads 0 on the sound run and 0.483 on the control; the sound run
    # also passes the limit that codeqwen's cell commits
    ("wide-codeqwen", "wide-mix", 11, "committed"),
    # the open-loop Poisson test mix (prompts 128-2048 in 32-row chunks)
    # at 512 wide, against the limit codeqwen's cell commits too (at test
    # widths the control reads as the sound run on its 80 tokens)
    ("wide-codeqwen", "poisson-mix", 11, "committed")])
def test_sound_run_correct_and_control_fails(name, mix, seed, limits,
                                             monkeypatch):
    replayed, gaps_fn = _spy(monkeypatch)
    limits = cells.check() if limits == "test" \
        else cells.with_committed(name)
    out = cells.run(name, seed, mix=mix, limits=limits)
    assert out["correct"] is True, out["check"]
    assert out["failed"] == 0
    assert out["attempted"] == traffic.n_requests(cells.mix(mix), 1.0)
    assert list(out)[-1] == "check"
    assert set(limits) <= set(out["check"])
    assert {"setup_s", "tpot_p95_ms"} <= set(out["metrics"])
    # the control: the reference at `high` picks the tokens
    sound, control = _control(replayed, gaps_fn, seed)
    assert all(sound[k] == out["check"][k]["value"] for k in limits)
    assert cells.over_limit(control, limits), (control, limits)
