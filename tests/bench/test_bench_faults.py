"""A run with the timed path broken underneath comes out not correct: the
harness's whole run at test widths on the CPU, the look for a chip
skipped. Each fault fails the test cells' limit and also the limit that
the benchmark's own cell of the same family commits."""
import pytest

import _cpu_cells as cells


@pytest.mark.parametrize("name", ["smoke-codeqwen", "smoke-rwkv6"])
@pytest.mark.parametrize("fault", sorted(cells.FAULTS))
def test_fault_comes_out_not_correct(name, fault):
    out = cells.run(name, 2**31 + 5, hook=cells.FAULTS[fault],
                    limits=cells.with_committed(name))
    assert out["correct"] is False, out["check"]
    numbers = {k: v["value"] for k, v in out["check"].items()}
    assert cells.over_limit(numbers, cells.check()), out["check"]
    committed = cells.committed_check(name)
    assert cells.over_limit(numbers, committed), (out["check"], committed)


@pytest.mark.parametrize("fault", sorted(cells.FAULTS))
def test_fault_on_the_open_loop_mix(fault):
    """The same faults under the Poisson test mix (data/poisson-mix.json,
    long prompts in 32-row chunks, a few slots of 16 live at a time), held
    to the test cells' limits and to what codeqwen's cell commits."""
    committed = cells.committed_check("smoke-codeqwen")
    out = cells.run("smoke-codeqwen", 2**31 + 6, hook=cells.FAULTS[fault],
                    mix="poisson-mix", seconds=2.0,
                    limits=dict(cells.check(), **committed))
    assert out["correct"] is False, out["check"]
    numbers = {k: v["value"] for k, v in out["check"].items()}
    assert cells.over_limit(numbers, cells.check()), out["check"]
    assert cells.over_limit(numbers, committed), (out["check"], committed)
