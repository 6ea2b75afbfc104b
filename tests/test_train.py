"""Closed ML-scheduling training loop tests (paper contribution (5),
repro.ml.train + the Scenario.alpha / JobTable.ml_basis machinery)."""
import copy

import numpy as np
import jax

from conftest import make_jobs
from repro.core import engine as eng
from repro.core import types as T
from repro.ml import scoring
from repro.ml import train as ml_train
from repro.ml.pipeline import MLSchedulerModel, attach_basis, attach_scores
from repro.systems.config import get_system

SYS = get_system("marconi100").scaled(64)
T1 = 3600.0


def _fitted(seed=7, n_jobs=90, load=1.6):
    js = make_jobs(SYS, seed=seed, n_jobs=n_jobs, load=load,
                   duration_s=T1, mean_wall_s=3600.0, prepop=False)
    model = MLSchedulerModel.fit(js, k=3, n_trees=4, depth=4, seed=0)
    return js, model


def test_score_is_linear_in_alpha():
    feats = np.abs(np.random.default_rng(0).normal(
        100.0, 50.0, (40, scoring.K_SCORE)))
    a1 = np.asarray([1.0, 0.5, 2.0, 0.1], np.float32)
    a2 = np.asarray([0.2, 1.5, 0.0, 1.0], np.float32)
    s_sum = scoring.score(feats, a1 + a2)
    s_parts = scoring.score(feats, a1) + scoring.score(feats, a2)
    np.testing.assert_allclose(np.asarray(s_sum), np.asarray(s_parts),
                               rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(scoring.score(feats, a1)),
        np.asarray(scoring.basis(feats)) @ a1, rtol=1e-5)


def test_alpha_scenario_matches_baked_score_static_parity():
    """Scenario.alpha on a basis table == attach_scores + simulate_static:
    the traced parameterization reproduces the legacy path bit-for-bit."""
    js, model = _fitted()
    js_baked = copy.deepcopy(js)
    attach_scores(js_baked, model)
    t_baked = js_baked.to_table()
    js_basis = copy.deepcopy(js)
    attach_basis(js_basis, model)
    t_basis = js_basis.to_table()

    f_static, h_static = eng.simulate_static(SYS, t_baked, "ml",
                                             "first-fit", 0.0, T1)
    f_alpha, h_alpha = eng.simulate(
        SYS, t_basis,
        T.Scenario.make("ml", "first-fit", alpha=np.asarray(model.alpha)),
        0.0, T1)
    np.testing.assert_array_equal(np.asarray(f_static.jstate),
                                  np.asarray(f_alpha.jstate))
    np.testing.assert_allclose(np.asarray(f_static.start),
                               np.asarray(f_alpha.start))
    np.testing.assert_allclose(np.asarray(h_static.power_it),
                               np.asarray(h_alpha.power_it))


def test_neutral_alpha_keeps_legacy_ranking():
    """alpha=0 on a basis-carrying table must not disturb non-ml policies
    (and leaves the ml key at the baked score)."""
    js, model = _fitted()
    attach_basis(js, model)
    table = js.to_table()
    f1, _ = eng.simulate(SYS, table, T.Scenario.make("fcfs", "first-fit"),
                         0.0, T1)
    f2, _ = eng.simulate_static(SYS, table, "fcfs", "first-fit", 0.0, T1)
    np.testing.assert_array_equal(np.asarray(f1.jstate),
                                  np.asarray(f2.jstate))


def test_es_generation_is_seeded_deterministic():
    """Same seed -> bit-identical candidates, rewards and updated mean."""
    js, model = _fitted()
    attach_basis(js, model)
    table = js.to_table()
    runs = []
    for _ in range(2):
        res = ml_train.train(SYS, table, 0.0, T1, reward="wait=1",
                             generations=1, population=4, sigma=0.3,
                             lr=0.5, seed=123, checkpoint=None, log=None)
        runs.append(res)
    np.testing.assert_array_equal(runs[0].mu, runs[1].mu)
    assert runs[0].reward_best == runs[1].reward_best
    assert runs[0].history[0]["reward_mu"] == runs[1].history[0]["reward_mu"]


def test_antithetic_population_structure():
    rng = np.random.default_rng(0)
    mu = np.asarray([1.0, 1.0, 1.0, 0.5])
    pop = ml_train.antithetic_population(mu, 0.3, rng, 8)
    assert pop.shape == (8, 4)
    # antithetic pairing: row i and row i+4 mirror around mu
    np.testing.assert_allclose(pop[:4] + pop[4:],
                               np.broadcast_to(2 * mu, (4, 4)), atol=1e-6)


def test_centered_ranks_and_es_update_direction():
    """The ES step must move mu toward the better antithetic twin."""
    mu = np.zeros(2)
    eps = np.asarray([[1.0, 0.0]])
    cands = np.concatenate([mu + 0.5 * eps, mu - 0.5 * eps], 0)
    # +eps wins -> mu should move in +eps direction
    new = ml_train.es_update(mu, cands, np.asarray([1.0, 0.0]), 0.5, 1.0)
    assert new[0] > 0.0 and abs(new[1]) < 1e-12
    u = ml_train.centered_ranks(np.asarray([3.0, -1.0, 7.0]))
    assert u.min() == -0.5 and u.max() == 0.5 and abs(u.sum()) < 1e-12


def test_trained_alpha_beats_default_on_its_objective():
    """Reward monotonicity: the elite returned by train() achieves at
    least the hand-set DEFAULT_ALPHA's reward on the training objective
    (the baseline rides in every batched generation), and on this seeded
    workload strictly improves it."""
    from repro.datasets.loaders import load_marconi100
    js = load_marconi100(n_jobs=90, days=0.1, seed=0)
    js = js.select(np.asarray(js.nodes) <= SYS.n_nodes)  # as the CLI does
    model = MLSchedulerModel.fit(js, k=4, n_trees=6, depth=5, seed=0)
    attach_basis(js, model)
    js.assign_prepop_placement(0.0, SYS.n_nodes)
    table = js.to_table()
    res = ml_train.train(SYS, table, 0.0, 7200.0,
                         reward="wait=1,turnaround=0.5", generations=3,
                         population=8, sigma=0.35, lr=0.8, seed=0,
                         checkpoint=None, log=None)
    assert res.reward_best >= res.reward_default
    assert res.reward_best > res.reward_default, \
        "ES failed to improve on the default alpha on the seeded workload"
    # baseline normalization: the default-alpha reward is exactly -sum(w)
    assert abs(res.reward_default - (-1.5)) < 1e-9


def test_one_generation_is_one_batched_rollout():
    """No Python loop over candidates: a generation with population P
    enters the engine exactly once (population + mean + baseline rows on
    the scenario axis of a single sweep)."""
    js, model = _fitted()
    attach_basis(js, model)
    table = js.to_table()
    calls = []
    orig = eng.simulate_sweep

    def spy(system, table_, scens, *a, **kw):
        calls.append(len(scens))
        return orig(system, table_, scens, *a, **kw)

    old_sharded = eng.simulate_sweep_sharded
    try:
        eng.simulate_sweep = spy
        # sharded falls through to simulate_sweep on one device; spy both
        eng.simulate_sweep_sharded = spy
        ml_train.train(SYS, table, 0.0, T1, reward="wait=1",
                       generations=2, population=6, sigma=0.3, lr=0.5,
                       seed=0, checkpoint=None, log=None)
    finally:
        eng.simulate_sweep = orig
        eng.simulate_sweep_sharded = old_sharded
    assert calls == [8, 8]   # one rollout per generation, P + 2 rows each


def test_checkpoint_resume_roundtrip(tmp_path):
    """A resumed run continues the trajectory exactly where it stopped."""
    js, model = _fitted()
    attach_basis(js, model)
    table = js.to_table()
    ck = tmp_path / "ck.json"
    kw = dict(reward="wait=1", population=4, sigma=0.3, lr=0.5, seed=5,
              log=None)
    full = ml_train.train(SYS, table, 0.0, T1, generations=3,
                          checkpoint=None, **kw)
    ml_train.train(SYS, table, 0.0, T1, generations=2, checkpoint=ck, **kw)
    resumed = ml_train.train(SYS, table, 0.0, T1, generations=3,
                             checkpoint=ck, resume=True, **kw)
    np.testing.assert_allclose(resumed.mu, full.mu, rtol=1e-12)
    assert resumed.reward_best == full.reward_best
    assert ml_train.load_alpha(ck).shape == (scoring.K_SCORE,)


def test_reward_spec_parsing():
    r = ml_train.Reward.parse("wait=2, energy=0.5 ,pue")
    assert dict(r.weights) == {"wait": 2.0, "energy": 0.5, "pue": 1.0}
    import pytest
    with pytest.raises(ValueError):
        ml_train.Reward.parse("no_such_metric=1")
    with pytest.raises(ValueError):
        ml_train.Reward.parse("")


def test_train_cli_smoke_improves_reward(tmp_path):
    """`simulate train --smoke` end to end: asserts internally that the
    trained reward improves on the default alpha and writes a checkpoint;
    the subcommand exits 0."""
    import json

    from repro.launch import simulate as cli
    ck = tmp_path / "smoke.json"
    assert cli.main(["train", "--smoke", "--checkpoint", str(ck)]) == 0
    state = json.loads(ck.read_text())
    assert state["best_reward"] > state["history"][-1]["reward_baseline"]
    # the checkpointed elite reloads as a finite alpha vector
    alpha = ml_train.load_alpha(ck)
    np.testing.assert_array_equal(alpha, np.asarray(state["best_alpha"],
                                                    np.float32))
    assert np.all(np.isfinite(alpha))


def test_sweep_population_rows_are_independent():
    """Batched rows match solo runs: evaluating [a_default, a_other] in
    one sweep gives the same telemetry as two single simulations."""
    js, model = _fitted()
    attach_basis(js, model)
    table = js.to_table()
    a0 = np.asarray(model.alpha)
    a1 = np.asarray([2.0, 0.2, 0.4, 1.5], np.float32)
    finals, hists = eng.simulate_sweep(
        SYS, table,
        [T.Scenario.make("ml", "first-fit", alpha=a0),
         T.Scenario.make("ml", "first-fit", alpha=a1)], 0.0, T1)
    for i, a in enumerate([a0, a1]):
        f_solo, h_solo = eng.simulate(
            SYS, table, T.Scenario.make("ml", "first-fit", alpha=a),
            0.0, T1)
        np.testing.assert_allclose(
            np.asarray(hists.power_it)[i], np.asarray(h_solo.power_it))
        pick = jax.tree_util.tree_map(lambda x, i=i: x[i], finals)
        np.testing.assert_array_equal(np.asarray(pick.jstate),
                                      np.asarray(f_solo.jstate))
