"""Checkpoint-parity oracle for the twin service (repro.serve).

The serving architecture rests on one claim: a trajectory advanced in
interval-sized segments — with the carry serialized to JSON and decoded
back between every pair of segments — is **bit-identical** to the same
trajectory as one uninterrupted ``lax.scan``. Not "close", identical:
the scan body is the same ``engine_step`` and grid/weather inputs are
gathered at the carry's absolute step cursor, so segmentation must be
unobservable. These tests assert exact equality (``np.array_equal``, no
tolerances) on every telemetry field and every final-carry leaf, for a
flat plant and a 4-hall topology, with time-varying grid signals and
weather in the loop so the absolute-step gather is actually exercised
across segment boundaries.

Also here: the neutral-delta fork oracle (a fork that changes nothing
must *be* its parent, row for row) and the LRU regression test for the
runner cache a long-lived server leans on.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import (assert_trees_equal, concat_hists, make_case,
                      make_signals)
from repro.core import engine as eng
from repro.core import types as T
from repro.cooling import weather as wsig
from repro.launch.simulate import build_system
from repro.serve import session as serve_session
from repro.serve import snapshot as snap

INTERVAL = 8          # engine steps per segment
N_INTERVALS = 6
HORIZON = INTERVAL * N_INTERVALS


@pytest.fixture(scope="module", params=["flat", "halls"])
def topo_case(request):
    """(system, table, scenario, signals, weather) for both plant shapes."""
    if request.param == "flat":
        system = build_system("marconi100", scale=64)
        scen = T.Scenario.make("fcfs", "easy", setpoint_delta_c=1.0)
    else:
        system = build_system("marconi100", scale=64, halls=4)
        scen = T.Scenario.make("thermal_aware", "firstfit",
                               cells_offline=(1.0, 0.0, 0.0, 0.0))
    js, table = make_case(system)
    signals = make_signals(system, HORIZON)
    weather = wsig.synthetic_weather(HORIZON, system.dt, seed=5)
    return system, table, scen, signals, weather


@pytest.mark.timeout(300)
def test_segmented_resume_is_bit_identical(topo_case):
    """Segment at EVERY interval boundary, serialize/deserialize the
    carry between segments, and require the concatenated telemetry and
    the final carry to match one uninterrupted ``simulate`` bitwise."""
    system, table, scen, signals, weather = topo_case
    t1 = HORIZON * system.dt
    ref_final, ref_hist = eng.simulate(system, table, scen, 0.0, t1,
                                       num_accounts=8, signals=signals,
                                       weather=weather)

    carry = eng.init_state(system, table, 0.0, t1, num_accounts=8)
    hists = []
    for _ in range(N_INTERVALS):
        # the wire trip a served checkpoint takes: encode -> JSON text ->
        # decode against the template (strict JSON, byte-faithful)
        payload = json.loads(json.dumps(snap.encode_carry(carry)))
        carry = snap.decode_carry(payload, carry)
        carry, hist = eng.simulate_segment(system, table, carry, scen,
                                           INTERVAL, signals, weather)
        hists.append(hist)

    assert_trees_equal(concat_hists(hists), ref_hist, "telemetry")
    assert_trees_equal(carry, ref_final, "final carry")


@pytest.mark.timeout(300)
def test_neutral_fork_equals_parent(topo_case):
    """A fork with an empty Scenario delta IS its parent: same rows,
    same checkpoints, same snapshot digests, from the fork point on."""
    system, table, scen, signals, weather = topo_case
    t1 = HORIZON * system.dt
    sess = serve_session.TwinSession(system, table, scen, 0.0, t1,
                                     interval_steps=INTERVAL,
                                     signals=signals, weather=weather,
                                     num_accounts=8)
    sess.advance_many({0: 2})
    child = sess.fork(0, {})                    # neutral delta
    sess.advance_many({0: N_INTERVALS - 2,
                       child.branch_id: N_INTERVALS - 2})

    parent_rows = {r["step"]: r for r in sess.fetch(0)["rows"]}
    child_rows = sess.fetch(child.branch_id)["rows"]
    assert len(child_rows) == HORIZON - child.born_step
    for row in child_rows:
        assert row == parent_rows[row["step"]], f"step {row['step']}"

    for step in sess.branches[child.branch_id].checkpoints:
        assert (sess.snapshot(0, at_step=step)["digest"]
                == sess.snapshot(child.branch_id, at_step=step)["digest"])


@pytest.mark.timeout(300)
def test_divergent_fork_shares_prefix_and_diverges(topo_case):
    """Sanity for the other direction: a *non*-neutral delta must match
    the parent before the fork point and actually change the physics
    after it (a delta the engine ignores would make every parity test
    above pass vacuously)."""
    system, table, scen, signals, weather = topo_case
    t1 = HORIZON * system.dt
    sess = serve_session.TwinSession(system, table, scen, 0.0, t1,
                                     interval_steps=INTERVAL,
                                     signals=signals, weather=weather,
                                     num_accounts=8)
    sess.advance_many({0: 3})
    child = sess.fork(0, {"setpoint_delta_c": 4.0})
    sess.advance_many({0: 3, child.branch_id: 3})
    parent = {r["step"]: r for r in sess.fetch(0)["rows"]}
    child_rows = sess.fetch(child.branch_id)["rows"]
    assert any(row != parent[row["step"]] for row in child_rows), \
        "setpoint_delta_c=4.0 produced bit-identical telemetry"
    # and the shared prefix stayed shared: fork point checkpoint digests
    assert (sess.snapshot(0, at_step=child.born_step)["digest"]
            == sess.snapshot(child.branch_id,
                             at_step=child.born_step)["digest"])


@pytest.mark.timeout(120)
def test_fork_from_earlier_checkpoint(topo_case):
    """Forking at a historical boundary resumes from *that* carry: the
    child's first telemetry rows equal the parent's rows at those steps
    (neutral delta), even though the parent is far ahead by then."""
    system, table, scen, signals, weather = topo_case
    t1 = HORIZON * system.dt
    sess = serve_session.TwinSession(system, table, scen, 0.0, t1,
                                     interval_steps=INTERVAL,
                                     signals=signals, weather=weather,
                                     num_accounts=8)
    sess.advance_many({0: N_INTERVALS})        # run the root to the end
    child = sess.fork(0, {}, at_step=INTERVAL)  # rewind to boundary 1
    assert child.step == INTERVAL
    sess.advance_many({child.branch_id: 2})
    parent = {r["step"]: r for r in sess.fetch(0)["rows"]}
    for row in sess.fetch(child.branch_id)["rows"]:
        assert row == parent[row["step"]], f"step {row['step']}"


@pytest.mark.timeout(120)
def test_checkpoints_live_on_host(topo_case):
    """Interval checkpoints are moved to host numpy on commit — a
    long-lived session holds one per tick per branch, and only the live
    carry should pin device memory. Forking from a host checkpoint is
    still bit-identical (the parity tests above run through this path)."""
    system, table, scen, signals, weather = topo_case
    t1 = HORIZON * system.dt
    sess = serve_session.TwinSession(system, table, scen, 0.0, t1,
                                     interval_steps=INTERVAL,
                                     signals=signals, weather=weather,
                                     num_accounts=8)
    sess.advance_many({0: 2})
    child = sess.fork(0, {})
    sess.advance_many({child.branch_id: 1})
    for br in sess.branches.values():
        assert len(br.checkpoints) >= 2
        for step, ck in br.checkpoints.items():
            for leaf in jax.tree_util.tree_leaves(ck):
                assert isinstance(leaf, np.ndarray), \
                    f"branch {br.branch_id} step {step}: device leaf"


def test_rejects_partial_interval_horizon(topo_case):
    """A horizon that is not a whole number of intervals has an
    unreachable tail (advances land on interval boundaries) — the
    session must refuse it loudly instead of silently stopping short."""
    system, table, scen, signals, weather = topo_case
    with pytest.raises(ValueError, match="multiple of interval_steps"):
        serve_session.TwinSession(system, table, scen, 0.0,
                                  (HORIZON + 1) * system.dt,
                                  interval_steps=INTERVAL)


# ---------------------------------------------------------------------------
# Satellite: the runner cache must stay bounded under a long-lived server.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("limit, n_keys", [(4, 10), (2, 4)])
def test_sweep_cache_lru_bound(monkeypatch, limit, n_keys):
    """Regression: the one runner cache (``_SWEEP_CACHE``) evicts
    least-recently-used runners past ``SWEEP_CACHE_LIMIT`` (counted),
    instead of growing forever."""
    import collections
    monkeypatch.setattr(eng, "_SWEEP_CACHE", collections.OrderedDict())
    monkeypatch.setattr(eng, "SWEEP_CACHE_LIMIT", limit)
    monkeypatch.setattr(eng, "SWEEP_CACHE_STATS",
                        {"hits": 0, "misses": 0, "evictions": 0})

    for i in range(n_keys):
        assert eng._cache_lookup(("k", i)) is None
        eng._cache_store(("k", i), f"runner{i}")
    # survivors are the most recently stored
    survivors = [("k", i) for i in range(n_keys - limit, n_keys)]
    assert list(eng._SWEEP_CACHE) == survivors
    assert eng.SWEEP_CACHE_STATS == {"hits": 0, "misses": n_keys,
                                     "evictions": n_keys - limit}

    # a hit refreshes recency: the oldest survivor now outlives the next
    oldest = survivors[0]
    assert eng._cache_lookup(oldest) == f"runner{oldest[1]}"
    eng._cache_store(("k", 99), "runner99")
    assert list(eng._SWEEP_CACHE) == survivors[2:] + [oldest, ("k", 99)]
    assert eng.SWEEP_CACHE_STATS == {"hits": 1, "misses": n_keys,
                                     "evictions": n_keys - limit + 1}


def test_every_entry_compiles_into_the_one_bounded_cache(
        monkeypatch, small_system, small_table):
    """All six entries build their programs through one runner cache under
    one bound: the static fast path's runner is evicted like any other."""
    import collections
    monkeypatch.setattr(eng, "_SWEEP_CACHE", collections.OrderedDict())
    monkeypatch.setattr(eng, "SWEEP_CACHE_LIMIT", 3)
    monkeypatch.setattr(eng, "SWEEP_CACHE_STATS",
                        {"hits": 0, "misses": 0, "evictions": 0})
    system, table, n = small_system, small_table, 3
    t1 = n * system.dt
    scens = [T.Scenario.make("fcfs", "easy"), T.Scenario.make("sjf")]
    fresh = lambda: eng.init_state(system, table, 0.0, t1)
    calls = [
        lambda: eng.simulate(system, table, scens[0], 0.0, t1),
        lambda: eng.simulate_static(system, table, "sjf", "easy", 0.0, t1),
        lambda: eng.simulate_sweep(system, table, scens, 0.0, t1),
        lambda: eng.simulate_sweep_sharded(system, table, scens, 0.0, t1),
        lambda: eng.simulate_segment(system, table, fresh(), scens[1], n),
        lambda: eng.simulate_segment_sweep(system, table,
                                           [fresh(), fresh()], scens, n),
    ]
    ran = []
    for call in calls:
        jax.block_until_ready(call())
        ran.append(eng._last_runner())
    simulate, static, sweep, sharded, segment, segment_sweep = ran
    # on the one test device the sharded entry runs the sweep's program,
    # and a segment from a fresh carry runs simulate's
    assert sharded is sweep and segment is simulate
    # four programs in one cache of three: the least recently used one,
    # the static runner, went when the bound was passed
    assert list(eng._SWEEP_CACHE.values()) == [sweep, simulate,
                                               segment_sweep]
    assert static not in eng._SWEEP_CACHE.values()
    assert eng.SWEEP_CACHE_STATS == {"hits": 2, "misses": 4,
                                     "evictions": 1}


@pytest.mark.timeout(300)
def test_sweep_cache_lru_bound_end_to_end(monkeypatch):
    """Same bound through the public API: many distinct segment lengths
    (what a server with many interval configs would compile) never hold
    more than the limit, and evicted runners re-compile on demand."""
    import collections
    monkeypatch.setattr(eng, "_SWEEP_CACHE", collections.OrderedDict())
    monkeypatch.setattr(eng, "SWEEP_CACHE_LIMIT", 3)
    monkeypatch.setattr(eng, "SWEEP_CACHE_STATS",
                        {"hits": 0, "misses": 0, "evictions": 0})
    system = build_system("marconi100", scale=64)
    _, table = make_case(system, n_jobs=16, pad=24)
    scen = T.Scenario.make("fcfs")
    carry = eng.init_state(system, table, 0.0, 64 * system.dt,
                           num_accounts=8)
    step0 = int(carry.step)
    # simulate_segment *donates* the carry it is given (the scan writes
    # in place — engine.DONATE_CARRIES), so each call gets its own copy
    fresh = lambda: jax.tree_util.tree_map(jnp.copy, carry)
    for n in (1, 2, 3, 4, 5):
        eng.simulate_segment(system, table, fresh(), scen, n)
    assert len(eng._SWEEP_CACHE) == 3
    assert eng.SWEEP_CACHE_STATS["evictions"] == 2
    # the evicted n=1 runner comes back transparently (a fresh miss)
    misses_before = eng.SWEEP_CACHE_STATS["misses"]
    out, _ = eng.simulate_segment(system, table, fresh(), scen, 1)
    assert int(out.step) == step0 + 1
    assert eng.SWEEP_CACHE_STATS["misses"] == misses_before + 1
