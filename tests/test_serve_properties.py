"""Property suite for the twin service: fork trees and the snapshot codec.

Two invariants carry the whole serving design:

1. **Fork-tree oracle.** However a branch came to be — forked from a
   fork of a fork, at random interval boundaries, with random Scenario
   deltas, advanced through the session's coalescing batcher — its
   telemetry must equal a *phase-wise oracle*: one plain
   ``simulate_segment`` per tree edge, no segmentation, no
   serialization, no batching. Exact float equality, not tolerance.
2. **Snapshot codec.** ``encode_carry``/``decode_carry`` roundtrip any
   carry byte-faithfully (including NaN/±inf bit patterns) through
   strict JSON, malformed payloads fail with ``SnapshotError`` (never
   anything else), and a Frontier-scale snapshot reply still fits the
   transport's ``MAX_FRAME_BYTES`` frame cap.

The randomized exploration runs under hypothesis where installed (CI:
requirements-dev.txt); the same properties are also exercised with
fixed seeds so the oracle runs everywhere.
"""
import io
import json
import random

import jax
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core import transport as tr
from repro.core import types as T
from repro.datasets.synthetic import WorkloadSpec, generate
from repro.serve import protocol as proto
from repro.serve import snapshot as snap
from repro.serve.session import TwinSession
from repro.systems.config import get_system

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:       # local runs without the dev extras
    HAS_HYPOTHESIS = False

    class _AnyStrategy:
        """Inert stand-in so @given/strategy expressions still import."""
        def __call__(self, *a, **k):
            return self

        def __or__(self, other):
            return self

        def __getattr__(self, name):
            return self

    st = _AnyStrategy()

    def given(*a, **k):
        return lambda f: f

    settings = given

needs_hypothesis = pytest.mark.skipif(
    not HAS_HYPOTHESIS, reason="hypothesis not installed")

INTERVAL = 6
MAX_INTERVALS = 4
HORIZON = INTERVAL * MAX_INTERVALS

# knobs a random fork delta may draw from, with their value ranges
KNOB_DRAWS = {
    "setpoint_delta_c": (-3.0, 3.0),
    "cap_scale": (0.7, 1.2),
    "cells_offline": (0.0, 2.0),
    "alpha": (-1.0, 1.0),
}


@pytest.fixture(scope="module")
def case():
    system = get_system("marconi100").scaled(64)
    js = generate(system, WorkloadSpec(
        n_jobs=48, duration_s=2 * 3600.0, load=1.2, trace_len=8,
        n_accounts=8, mean_wall_s=1200.0, seed=9))
    js.assign_prepop_placement(0.0, system.n_nodes)
    return system, js.to_table(64)


def random_delta(rng: random.Random) -> dict:
    knobs = rng.sample(sorted(KNOB_DRAWS), rng.randint(1, 2))
    return {k: round(rng.uniform(*KNOB_DRAWS[k]), 3) for k in knobs}


def build_random_tree(rng: random.Random, sess: TwinSession,
                      n_forks: int) -> None:
    """Random interleaving of advances and forks against ``sess``."""
    for _ in range(n_forks):
        # advance a random subset of branches a random number of ticks
        branches = list(sess.branches)
        picks = rng.sample(branches, rng.randint(1, len(branches)))
        sess.advance_many({b: rng.randint(1, 2) for b in picks})
        parent = rng.choice(branches)
        ck = sorted(sess.branches[parent].checkpoints)
        sess.fork(parent, random_delta(rng), at_step=rng.choice(ck))
    # run every branch out to the horizon so each leaf has history
    sess.advance_many({b: MAX_INTERVALS for b in sess.branches})


def oracle_rows(sess: TwinSession, branch_id: int):
    """Phase-wise oracle for one branch: replay its ancestry with one
    plain ``simulate_segment`` per tree edge, return the branch's own
    rows (born_step .. step) in the session's fetch format."""
    system, table = sess.system, sess.table
    chain = []
    b = sess.branches[branch_id]
    while b is not None:
        chain.append(b)
        b = sess.branches[b.parent] if b.parent is not None else None
    chain.reverse()

    carry = eng.init_state(system, table, sess.t0, sess.t1, num_accounts=8)
    rows = []
    pos = 0
    leaf = chain[-1]
    for k, edge in enumerate(chain):
        stop = leaf.step if edge is leaf else chain[k + 1].born_step
        if stop == pos:
            continue
        carry, hist = eng.simulate_segment(system, table, carry,
                                           edge.scenario, stop - pos,
                                           sess.signals, sess.weather)
        if edge is leaf:
            from repro.obs import sink as obs_sink
            cat = {k: np.asarray(getattr(hist, k), np.float64)
                   for k in ("t",) + obs_sink.SCALAR_FIELDS}
            skip = leaf.born_step - pos
            for i in range(skip, stop - pos):
                row = {"step": pos + i}
                row.update({k: float(v[i]) for k, v in cat.items()})
                rows.append(row)
        pos = stop
    return rows


def check_fork_tree(case, seed: int, n_forks: int) -> None:
    system, table = case
    rng = random.Random(seed)
    sess = TwinSession(system, table, T.Scenario.make("fcfs", "easy"),
                       0.0, HORIZON * system.dt, interval_steps=INTERVAL,
                       num_accounts=8)
    build_random_tree(rng, sess, n_forks)
    assert len(sess.branches) == n_forks + 1
    for branch_id in sess.branches:
        got = sess.fetch(branch_id)["rows"]
        want = oracle_rows(sess, branch_id)
        assert len(got) == len(want), f"branch {branch_id}"
        for g, w in zip(got, want):
            assert g == w, (f"branch {branch_id} step {g['step']}: "
                            f"{g} != {w}")


@pytest.mark.timeout(600)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fork_tree_matches_phasewise_oracle(case, seed):
    check_fork_tree(case, seed, n_forks=3)


@needs_hypothesis
@pytest.mark.timeout(600)
@given(seed=st.integers(0, 2**32 - 1), n_forks=st.integers(1, 4))
@settings(max_examples=5, deadline=None)
def test_fork_tree_matches_phasewise_oracle_hypothesis(case, seed, n_forks):
    check_fork_tree(case, seed, n_forks)


# ---------------------------------------------------------------------------
# Snapshot codec properties.
# ---------------------------------------------------------------------------
def randomized_carry(template, seed: int):
    """A carry with every leaf's bytes randomized (same dtype/shape),
    seasoned with NaN/±inf in the float leaves — the adversarial case
    for a JSON codec, trivial for a raw-bytes one."""
    rng = np.random.default_rng(seed)
    def scramble(x):
        a = np.asarray(x)
        if np.issubdtype(a.dtype, np.floating):
            out = rng.normal(size=a.shape).astype(a.dtype)
            flat = out.reshape(-1)
            if flat.size >= 4:
                flat[0], flat[1], flat[2] = np.nan, np.inf, -np.inf
            return flat.reshape(a.shape)
        info = np.iinfo(a.dtype)
        return rng.integers(info.min, info.max, size=a.shape,
                            dtype=a.dtype, endpoint=True)
    return jax.tree_util.tree_map(scramble, template)


def check_roundtrip(template, seed: int) -> None:
    carry = randomized_carry(template, seed)
    payload = json.loads(json.dumps(snap.encode_carry(carry)))
    out = snap.decode_carry(payload, template)
    for (p, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(carry)[0],
            jax.tree_util.tree_flatten_with_path(out)[0]):
        a, b = np.asarray(a), np.asarray(b)
        assert a.tobytes() == b.tobytes(), jax.tree_util.keystr(p)
    # digest is a function of the bytes alone: stable across re-encodes
    assert (snap.snapshot_digest(snap.encode_carry(out))
            == snap.snapshot_digest(payload))


@pytest.fixture(scope="module")
def template(case):
    system, table = case
    return eng.init_state(system, table, 0.0, HORIZON * 20.0,
                          num_accounts=8)


@pytest.mark.parametrize("seed", [0, 7, 1234])
def test_snapshot_roundtrip_byte_faithful(template, seed):
    check_roundtrip(template, seed)


@needs_hypothesis
@given(seed=st.integers(0, 2**32 - 1))
@settings(max_examples=25, deadline=None)
def test_snapshot_roundtrip_byte_faithful_hypothesis(template, seed):
    check_roundtrip(template, seed)


@needs_hypothesis
@given(st.recursive(
    st.none() | st.booleans() | st.integers() | st.floats(allow_nan=False)
    | st.text(max_size=8),
    lambda inner: st.lists(inner, max_size=4)
    | st.dictionaries(st.text(max_size=8), inner, max_size=4),
    max_leaves=20))
@settings(max_examples=100, deadline=None)
def test_decode_rejects_garbage_with_snapshot_error(template, payload):
    """Whatever JSON arrives, decode either succeeds or raises
    ``SnapshotError`` — never KeyError/TypeError/ValueError leakage."""
    try:
        snap.decode_carry(payload, template)
    except snap.SnapshotError:
        pass


def test_decode_rejects_wrong_shape_and_version(template):
    good = snap.encode_carry(template)
    with pytest.raises(snap.SnapshotError, match="version"):
        snap.decode_carry({**good, "v": 99}, template)
    mangled = json.loads(json.dumps(good))
    mangled["leaves"]["t"]["shape"] = [3]
    with pytest.raises(snap.SnapshotError):
        snap.decode_carry(mangled, template)
    # the node map, its per-node end times and its per-group summary are
    # all part of the carry
    for leaf in ("node_job", "node_end", "job_group_nodes"):
        dropped = json.loads(json.dumps(good))
        del dropped["leaves"][leaf]
        with pytest.raises(snap.SnapshotError, match=leaf):
            snap.decode_carry(dropped, template)


def test_scenario_delta_rejects_unknown_knobs():
    base = T.Scenario.make("fcfs")
    with pytest.raises(snap.SnapshotError, match="unknown scenario knob"):
        snap.apply_scenario_delta(base, {"warp_factor": 9})
    with pytest.raises(snap.SnapshotError):
        snap.apply_scenario_delta(base, {"policy": "telepathy"})
    with pytest.raises(snap.SnapshotError):
        snap.apply_scenario_delta(base, {"cap_scale": "big"})
    # and the happy path maps names to traced ids
    scen = snap.apply_scenario_delta(base, {"policy": "thermal_aware",
                                            "cap_scale": 0.9})
    assert int(scen.policy) == T.POLICY_NAMES["thermal_aware"]
    assert float(scen.cap_scale) == pytest.approx(0.9)


def test_scenario_delta_validates_vector_shapes():
    """A delta that would reshape a traced knob must fail at fork time
    with ``SnapshotError`` — not escape into the server's coalesced
    sweep and kill the executor as a JAX trace error (the batch stacks
    every branch's scenario leaf-wise, so shapes must agree)."""
    flat = T.Scenario.make("fcfs")                          # scalar knobs
    halls = T.Scenario.make("fcfs",
                            cells_offline=(0.0, 0.0, 0.0, 0.0))
    with pytest.raises(snap.SnapshotError, match="scalar in this session"):
        snap.apply_scenario_delta(flat, {"cells_offline": [1.0, 0.0]})
    with pytest.raises(snap.SnapshotError, match="length 4"):
        snap.apply_scenario_delta(halls, {"cells_offline": [1.0]})
    with pytest.raises(snap.SnapshotError, match="length 4"):
        snap.apply_scenario_delta(
            halls, {"cells_offline": [1.0, 0.0, 0.0, 0.0, 0.0]})
    with pytest.raises(snap.SnapshotError, match="scalar in this session"):
        snap.apply_scenario_delta(flat, {"alpha": [0.1, 0.2, 0.3]})
    # a matching-length vector keeps the shape ...
    out = snap.apply_scenario_delta(halls,
                                    {"cells_offline": [1.0, 0.0, 0.0, 0.0]})
    assert out.cells_offline.shape == (4,)
    # ... and a scalar broadcasts explicitly over a vector knob
    out = snap.apply_scenario_delta(halls, {"cells_offline": 2.0})
    assert out.cells_offline.shape == (4,)
    assert np.array_equal(np.asarray(out.cells_offline),
                          np.full(4, 2.0, np.float32))


@pytest.mark.timeout(300)
def test_frontier_scale_snapshot_fits_one_frame():
    """A full Frontier-scale carry (9408-node class system, 1k-job padded
    table), wrapped in a complete ``snapshot_ok`` reply envelope, must
    ride the existing transport framing — ``write_frame`` enforces
    ``MAX_FRAME_BYTES`` outbound, so this is the real cap, not an
    estimate."""
    system = get_system("frontier")
    js = generate(system, WorkloadSpec(
        n_jobs=512, duration_s=4 * 3600.0, load=1.0, trace_len=8,
        n_accounts=32, mean_wall_s=1800.0, seed=1))
    js.assign_prepop_placement(0.0, system.n_nodes)
    table = js.to_table(1024)
    carry = eng.init_state(system, table, 0.0, 4 * 3600.0,
                           num_accounts=64)
    payload = snap.encode_carry(carry)
    frame = proto.ok_frame("snapshot", 0, {
        "branch": 0, "step": 0, "snapshot": payload,
        "digest": snap.snapshot_digest(payload)})
    buf = io.BytesIO()
    counters = tr.WireCounters()
    tr.write_frame(buf, frame, counters)      # raises past the cap
    assert counters.frames_rejected == 0
    assert buf.tell() < tr.MAX_FRAME_BYTES
    # sanity: it decodes back bitwise
    out = snap.decode_carry(
        json.loads(buf.getvalue())["snapshot"], carry)
    for (p, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(carry)[0],
            jax.tree_util.tree_flatten_with_path(out)[0]):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
