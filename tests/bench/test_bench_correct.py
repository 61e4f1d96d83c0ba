"""``correct`` on a tiny Qwen2 on the CPU: the harness's whole run
(traffic, replay, engine, window, sample, float32 reference) without
its look for a chip.  A sound run passes and builds nothing in its
window; the fp8 control, put in the served tokens' place, a decode step
that returns its KV state unchanged, a step that leaves out half its
batch, and an altered token all fail."""

import json
import os

import jax.numpy as jnp
import pytest

from bench import run

DATA = os.path.join(os.path.dirname(__file__), "data")
SEEDS = (2**33 + 5, 41)


def load(name):
    with open(os.path.join(DATA, name + ".json")) as f:
        return json.load(f)


def tiny_cell():
    return run.Cell(
        name="tiny", chips=1, config=load("tiny"), mix=load("tiny_closed"),
        limits=load("tiny_limits"),
        end_to_end=[{"name": n, "unit": "x"} for n in
                    ("setup_s", "out_tok_s", "itl_p95_ms")],
        per_layer=[])


def go(seed=SEEDS[0], control=False):
    return run.run_cell(tiny_cell(), seed, 1.5, False, control=control,
                        compile_cache=False)


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_run_passes_and_the_fp8_control_fails(seed):
    res = go(seed, control=True)
    sound = res["program_checks"]
    assert sound["max_logit_gap"]["value"] <= sound["max_logit_gap"]["limit"]
    assert sound["compiles_in_window"]["value"] == 0
    assert sound["sampled_tokens"]["value"] >= sound["sampled_tokens"]["limit"]
    # the control, judged by the same checks in the served tokens' place
    assert not res["correct"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert res["checks"]["sampled_tokens"] == sound["sampled_tokens"]
    assert set(res["metrics"]) == {"setup_s", "out_tok_s", "itl_p95_ms"}
    assert list(res)[-1] == "checks"


def test_sound_run_is_correct_and_the_window_builds_nothing():
    res = go(SEEDS[1])
    assert res["correct"], res["checks"]
    assert res["checks"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert res["attempted"] > 0 and res["failed"] == 0


def test_two_passes_over_one_plan_run_the_same_steps():
    """The replay's premise: an engine's work follows from the plan and
    the step count alone."""
    cell = tiny_cell()
    _, model, params = run.build_program(cell, SEEDS[0])
    plan = run.traffic.plan(cell.mix, SEEDS[0], cell.config["vocab_size"])
    ecfg = run.engine_config(cell, plan)
    passes = []
    for _ in range(2):
        with run._lmb_system() as system:
            from repro.serve import ServeEngine
            driver = run.Driver(ServeEngine(model, params, system, ecfg),
                                plan, cell.mix)
            for _ in range(40):
                driver.step()
            passes.append(driver.steps)
    assert sum(s.prefills for s in passes[0]) > cell.mix["clients"]
    assert run.first_divergence(*passes) is None
    other = [run.Step(0, 0, [], 0)] + passes[1][1:]
    assert run.first_divergence(passes[0], other) == 0


def _patch_decode(monkeypatch, fault):
    from repro.models.zoo import Model
    real = Model.decode_step_paged

    def broken(self, params, pool, page_table, lengths, token):
        logits, new_pool = real(self, params, pool, page_table, lengths,
                                token)
        if fault == "state_unchanged":
            return logits, pool
        if fault == "half_batch":
            B = logits.shape[0]
            half = logits[:max(1, B // 2)]
            reps = -(-B // half.shape[0])
            return jnp.concatenate([half] * reps)[:B], new_pool
        if fault == "token_altered":
            return jnp.roll(logits, 1, axis=-1), new_pool
        raise ValueError(fault)

    monkeypatch.setattr(Model, "decode_step_paged", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    _patch_decode(monkeypatch, fault)
    res = go()
    assert not res["correct"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] is None or gap["value"] > gap["limit"]
