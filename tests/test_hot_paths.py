"""Scan-loop roofline paths: donated carries, compact job tables, and
the env-preset audit trail.

Buffer donation lets XLA write each scan step's carry in place instead
of allocating a fresh state tree per segment — but a donated input is
*consumed*, so every resume path must hand the runner a buffer it is
allowed to lose. These tests pin the contract: donation changes nothing
numerically, resume-from-segment stays bit-identical, the
``REPRO_NO_DONATE`` kill switch works, and the serve layer's checkpoint
templates survive their carries being eaten.
"""
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as eng
from repro.core import types as T
from repro.datasets.synthetic import WorkloadSpec, generate
from repro.systems.config import get_system

SYS = get_system("marconi100").scaled(32)


def make_table(seed=0, n=24, hours=1.0):
    js = generate(SYS, WorkloadSpec(n_jobs=n, duration_s=hours * 3600.0,
                                    load=1.2, trace_len=4, seed=seed))
    return js, js.to_table()


def tree_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
        for x, y in zip(la, lb))


def test_donation_enabled_by_default_and_killable():
    assert eng.DONATE_CARRIES is True
    assert eng._donate(1) == (1,)
    out = subprocess.run(
        [sys.executable, "-c",
         "from repro.core import engine as e; "
         "assert e.DONATE_CARRIES is False; "
         "assert e._donate(1) == ()"],
        env={**os.environ, "REPRO_NO_DONATE": "1",
             "PYTHONPATH": os.environ.get("PYTHONPATH", "src")},
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr


def test_segment_resume_bit_identical_to_unsegmented_run():
    """Two 30-step segments (carry donated between them) must equal one
    60-step run exactly — donation may not perturb a single bit."""
    _, table = make_table(seed=11)
    scen = T.Scenario.make("fcfs", "easy")
    n = 60
    t1 = n * SYS.dt
    whole = eng.init_state(SYS, table, 0.0, t1)
    whole, _ = eng.simulate_segment(SYS, table, whole, scen, 30)
    whole, hist2 = eng.simulate_segment(SYS, table, whole, scen, 30)

    ref = eng.init_state(SYS, table, 0.0, t1)
    ref, _ = eng.simulate_segment(SYS, table, ref, scen, n)
    assert tree_equal(whole, ref)
    assert float(whole.t) == float(ref.t)
    # the returned history covers the second half only
    assert np.asarray(hist2.power_total).shape[0] == 30


def test_donated_carry_is_consumed_and_copies_protect_it():
    """The perf contract made visible: after a segment run the input
    carry's buffers are gone (donated), and tree_map(copy) is the
    documented way to keep a live reference."""
    _, table = make_table(seed=12)
    scen = T.Scenario.make("fcfs", "easy")
    carry = eng.init_state(SYS, table, 0.0, 8 * SYS.dt)
    keep = jax.tree_util.tree_map(jnp.copy, carry)
    out, _ = eng.simulate_segment(SYS, table, carry, scen, 8)
    assert bool(carry.t.is_deleted()), \
        "carry not donated — the in-place scan path regressed"
    # the copy is untouched and resumable
    assert float(keep.t) == 0.0
    out2, _ = eng.simulate_segment(SYS, table, keep, scen, 8)
    assert tree_equal(out, out2)


def test_simulate_and_static_unaffected_by_donation():
    _, table = make_table(seed=13)
    scen = T.Scenario.make("fcfs", "easy")
    t1 = 40 * SYS.dt
    f1, h1 = eng.simulate(SYS, table, scen, 0.0, t1)
    f2, h2 = eng.simulate(SYS, table, scen, 0.0, t1)
    assert tree_equal(f1, f2) and tree_equal(h1, h2)
    s1 = eng.simulate_static(SYS, table, "fcfs", "first-fit", 0.0, t1)
    s2 = eng.simulate_static(SYS, table, "fcfs", "first-fit", 0.0, t1)
    assert tree_equal(s1[0], s2[0])


def test_warm_start_accounts_survive_two_donated_runs():
    """A caller-owned ledger passed via ``accounts=`` must not be eaten
    by donation: ``init_state`` copies it into the carry, so the same
    ledger can seed back-to-back runs (the collect-then-redeem flow)."""
    _, table = make_table(seed=5)
    final, _ = eng.simulate(SYS, table, T.Scenario.make("replay"),
                            0.0, 1800.0, num_accounts=4)
    acc = final.accounts
    f1, _ = eng.simulate(SYS, table, T.Scenario.make("fcfs", "easy"),
                         0.0, 1800.0, accounts=acc, num_accounts=4)
    f2, _ = eng.simulate(SYS, table, T.Scenario.make("fcfs", "easy"),
                         0.0, 1800.0, accounts=acc, num_accounts=4)
    assert not any(x.is_deleted() for x in jax.tree_util.tree_leaves(acc))
    assert tree_equal(f1.accounts, f2.accounts)


def test_account_ledger_leaves_are_distinct_buffers():
    """Donation requires every carry leaf to own its buffer; the ledger
    zeros must not alias one shared array across fields."""
    import dataclasses
    zeros = T.AccountStats.zeros(4)
    ptrs = set()
    for f in dataclasses.fields(T.AccountStats):
        leaf = getattr(zeros, f.name)
        ptrs.add(leaf.unsafe_buffer_pointer())
    assert len(ptrs) == len(dataclasses.fields(T.AccountStats)), \
        "AccountStats.zeros shares a buffer between fields"


# ---------------------------------------------------------------------------
# Compact job tables (int32 time columns behind the compat flag).
# ---------------------------------------------------------------------------
def test_compact_time_table_is_bit_compatible_end_to_end():
    js, _ = make_table(seed=14)
    # SWF contract: whole seconds
    for f in ("submit", "limit", "wall", "rec_start"):
        setattr(js, f, np.round(getattr(js, f)))
    t_f32 = js.to_table()
    t_i32 = js.to_table(compact_time=True)
    for f in ("submit", "limit", "wall", "rec_start"):
        assert getattr(t_i32, f).dtype == jnp.int32
    scen = T.Scenario.make("fcfs", "easy")
    t1 = 48 * SYS.dt
    f_a, h_a = eng.simulate(SYS, t_f32, scen, 0.0, t1)
    f_b, h_b = eng.simulate(SYS, t_i32, scen, 0.0, t1)
    assert tree_equal(f_a, f_b)
    assert tree_equal(h_a, h_b)


def test_compact_time_falls_back_to_f32_on_fractional_columns():
    js, _ = make_table(seed=15)
    js.submit = np.round(js.submit) + 0.25       # not whole seconds
    js.wall = np.round(js.wall)
    table = js.to_table(compact_time=True)
    assert table.submit.dtype == jnp.float32     # fell back
    assert table.wall.dtype == jnp.int32         # still narrowed
    # padded +inf spelling: sentinel on the int column, far past any t1
    padded = js.to_table(pad_to=len(js) + 3, compact_time=True)
    assert int(np.asarray(padded.rec_start)[-1]) == 1 << 30


# ---------------------------------------------------------------------------
# Env preset: report-only, embedded in manifests.
# ---------------------------------------------------------------------------
def test_env_preset_report_and_manifest_embedding(tmp_path):
    from repro.launch import env as launch_env
    from repro.obs import recorder as rec
    from repro.obs import schema

    rep = launch_env.report("throughput")
    assert rep["preset"] == "throughput"
    assert "XLA_FLAGS" in rep["recommended"]
    assert rep["allocator"] in ("tcmalloc", "jemalloc", "glibc",
                                "unknown")
    m = rec.build_manifest(SYS, "simulate", ["bench"], {},
                           extra={"env_preset": rep})
    assert m["env_preset"]["preset"] == "throughput"
    schema.validate_manifest(m)                  # extra keys validate

    import json
    json.dumps(m)                                # and serialize

    import pytest
    with pytest.raises(KeyError):
        launch_env.preset("nope")
    # apply() never clobbers what the user already exported
    os.environ["XLA_FLAGS"] = "--user-set"
    try:
        written = launch_env.apply("throughput")
        assert "XLA_FLAGS" not in written
        assert os.environ["XLA_FLAGS"] == "--user-set"
    finally:
        del os.environ["XLA_FLAGS"]


def test_compile_cache_helper_honours_env_else_fixed_checkout_path(
        monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.launch import env as launch_env
    root = pathlib.Path(__file__).resolve().parents[1]
    assert launch_env.COMPILE_CACHE_DIR == root / ".jax_cache"
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert launch_env.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = launch_env.enable_compile_cache()
        assert got == str(launch_env.COMPILE_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        # idempotent: a second entry point in the same process agrees
        assert launch_env.enable_compile_cache() == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()
