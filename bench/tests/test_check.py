"""``correct`` against the control and against planted faults, on the CPU
at the rehearsal width (``cpu_rehearsal`` in the configuration files).

Each run skips the look for a chip (``--reduced``) and drives the rest of
a run: set-up, the window, the reference check.  The sound program has
to come out correct; with the timed path broken underneath, or with the
fp8 control in the program's place, not correct.
"""
import jax
import jax.numpy as jnp
import pytest

import check
import control
import run as bench_run
import spec

CELL = "internlm2-1.8b.batch_sampled"
SECONDS = "8"
# At the rehearsal width (2 layers of 64) the fp8 control departs far less
# from the reference than at full width, where it reads 3 to 6.5 (PERF.md).
# Over 6 seeds of each cell on the CPU it read 0.043-0.156 wherever it
# departed at all, against the program's 0-0.011; at this size the control
# is held to a limit between those readings.
REHEARSAL_LIMIT = 0.03


def run_cell(seed, cell=CELL):
    jax.clear_caches()
    code, result = bench_run.execute(bench_run.parse(
        ["--workload", cell, "--seed", str(seed), "--seconds", SECONDS,
         "--trace", "0", "--reduced"]))
    assert code == 3 and result is not None
    return result


def test_sound_program_is_correct():
    result = run_cell(11)
    checks = result["checks"]
    assert "greedy_gap" in checks and "kept_gap" in checks, checks
    assert result["correct"], checks


def _state_unchanged(monkeypatch):
    from repro.serving import scheduler

    monkeypatch.setattr(scheduler, "freeze_cache_lanes",
                        lambda new, old, active: old)


def _half_batch(monkeypatch):
    from repro.serving import scheduler

    orig = scheduler.decode_step

    def half(cfg, params, token, pos, cache, **kw):
        logits, cache = orig(cfg, params, token, pos, cache, **kw)
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:logits.shape[0] - h]]), \
            cache

    monkeypatch.setattr(scheduler, "decode_step", half)


def _token_altered(monkeypatch):
    from repro.serving import scheduler

    orig = scheduler.sample_slots

    def altered(logits, *a, **kw):
        return (orig(logits, *a, **kw) + 1) % logits.shape[-1]

    monkeypatch.setattr(scheduler, "sample_slots", altered)


def _solve_broken(monkeypatch):
    from repro.serving import sampler

    monkeypatch.setattr(sampler, "topk_mask",
                        lambda z, k, **kw: jnp.ones(z.shape, bool))


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered, _solve_broken],
                         ids=lambda f: f.__name__.strip("_"))
def test_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    result = run_cell(12)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("cell", ["qwen3-4b.chat", CELL])
def test_fp8_control_is_not_correct(cell):
    """The reference in fp8 in the program's place fails a limit; the
    program, on the same prompts and tokens, passes the configuration's
    limits and the rehearsal one."""
    jax.clear_caches()
    out = []
    seconds = "8" if cell == CELL else "20"
    assert control.main(["--workload", cell, "--seconds", seconds,
                         "--seeds", "13", "--reduced"], out=out) == 0
    (found,) = out
    ok, _ = check.judge(found, spec.load_cell(cell).config["limits"],
                        found["bad_lengths"])
    assert ok and found["program_correct"], found
    at_size = {"greedy_gap": REHEARSAL_LIMIT, "kept_gap": REHEARSAL_LIMIT}
    assert check.judge(found, at_size, 0)[0], found
    low = {name: found["control_" + name] for name in ("greedy_gap",
                                                       "kept_gap")}
    assert not check.judge(low, at_size, 0)[0], low
