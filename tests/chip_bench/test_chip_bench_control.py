"""The comparison that decides ``correct`` fails where it must.

At small widths on the CPU (limits of the small configurations, set
from their own readings: sound runs read a widest gap of 0 to 0.0094,
the fp8 control 0.062 to 0.139; the small SC engine a cosine of 0.83 to
0.85, its bf16 control 0.99997):

* the control, the reference in the next lower precision put in the
  program's place and judged under the same limits, is not correct;
* a run whose timed path alters a token where it is produced, or whose
  step returns the KV cache unchanged, comes out not correct.

The same controls were read on the chip at each cell's own size
(``PERF.md``).
"""

import jax.numpy as jnp
import pytest

from benchmarks.chip import harness

from test_chip_bench_window import drive, tiny_cell

ARGS = ["--seed", "11", "--seconds", "1.5", "--trace", "0"]


def _altered(keys, logits, temperatures):
    """The engine's sampler with each token moved off the argmax."""
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (tok + 1) % logits.shape[-1]


@pytest.mark.parametrize("config", ["tiny_chat.json", "tiny_sc.json"])
def test_control_reads_outside_the_limit(monkeypatch, capsys, config):
    cell = tiny_cell(config=config)
    line, _ = drive(monkeypatch, capsys, cell,
                    ["--workload", cell.name, "--control", "1"] + ARGS)
    checks = line["checks"]
    assert line["correct"] is False and line["failed"] > 0
    if "max_gap" in checks:
        assert checks["max_gap"]["value"] > checks["max_gap"]["limit"]
    else:
        assert (checks["cos_mean_ceiling"]["value"]
                > checks["cos_mean_ceiling"]["limit"])


@pytest.mark.parametrize("config", ["tiny_chat.json", "tiny_sc.json"])
def test_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, capsys, config):
    import repro.serve.engine as engine_mod
    monkeypatch.setattr(engine_mod, "_sample_rows", _altered)
    cell = tiny_cell(config=config)
    line, err = drive(monkeypatch, capsys, cell,
                      ["--workload", cell.name] + ARGS)
    assert line["correct"] is False
    assert line["failed"] > 0
    assert err[-1].startswith("check ")


def test_a_step_that_returns_its_cache_unchanged_is_not_correct(
        monkeypatch, capsys):
    from repro.models import lm
    step = lm.decode_paged

    def stale(params, pages, *a, **k):
        logits, _ = step(params, pages, *a, **k)
        return logits, pages                 # the KV writes are dropped

    monkeypatch.setattr(lm, "decode_paged", stale)
    cell = tiny_cell()
    line, _ = drive(monkeypatch, capsys, cell,
                    ["--workload", cell.name] + ARGS)
    assert line["correct"] is False


def test_each_limit_compares_its_own_way():
    cell = tiny_cell()
    result = harness._checks(cell, {"max_gap": 0.0})
    assert all(harness._passes(k, v) for k, v in result.items())
    assert not harness._passes("max_gap", {"value": 0.03, "limit": 0.025})
    assert not harness._passes("cos_mean_floor", {"value": 0.6,
                                                  "limit": 0.7})
