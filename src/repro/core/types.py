"""Pytree state containers for the S-RAPS digital-twin engine.

Everything the simulation touches is a fixed-shape JAX array so the whole
forward-time loop compiles to a single ``lax.scan`` and batches of what-if
scenarios run under ``vmap`` / ``shard_map``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.obs import timing as obs_timing

# ---------------------------------------------------------------------------
# Job lifecycle states (values matter: they are stored in int32 arrays).
# ---------------------------------------------------------------------------
PENDING = 0     # known to the dataloader, not yet submitted (sim time < submit)
QUEUED = 1      # submitted, waiting for placement
RUNNING = 2     # placed on nodes
DONE = 3        # completed
DISMISSED = 4   # outside the simulation window (paper §3.2.2)

# Scheduling policies (paper §3.2.5 + §4.3 + §4.4). Traced integers so a
# vmapped scenario batch can sweep policies.
POLICY_REPLAY = 0
POLICY_FCFS = 1
POLICY_SJF = 2
POLICY_LJF = 3
POLICY_PRIORITY = 4
POLICY_ACCT_AVG_POWER = 5       # descending average account power
POLICY_ACCT_LOW_AVG_POWER = 6   # ascending average account power
POLICY_ACCT_EDP = 7             # ascending accumulated EDP
POLICY_ACCT_ED2P = 8            # ascending accumulated ED^2P
POLICY_ACCT_FUGAKU_PTS = 9      # descending Fugaku points (Solorzano et al.)
POLICY_ML = 10                  # ML-guided score S(X_i) (paper §4.4)
POLICY_CARBON = 11              # grid-aware: defer energy-heavy jobs while
                                # carbon intensity is above its rolling mean
POLICY_PRICE = 12               # analogous on the electricity-price signal
POLICY_THERMAL = 13             # cooling-aware: defer heat-dense jobs while
                                # the tower return temp approaches its limit

POLICY_NAMES = {
    "replay": POLICY_REPLAY,
    "fcfs": POLICY_FCFS,
    "sjf": POLICY_SJF,
    "ljf": POLICY_LJF,
    "priority": POLICY_PRIORITY,
    "acct_avg_power": POLICY_ACCT_AVG_POWER,
    "acct_low_avg_power": POLICY_ACCT_LOW_AVG_POWER,
    "acct_edp": POLICY_ACCT_EDP,
    "acct_ed2p": POLICY_ACCT_ED2P,
    "acct_fugaku_pts": POLICY_ACCT_FUGAKU_PTS,
    "ml": POLICY_ML,
    "carbon_aware": POLICY_CARBON,
    "price_aware": POLICY_PRICE,
    "thermal_aware": POLICY_THERMAL,
}

# Backfill modes (paper §3.2.5).
BF_NONE = 0       # strict in-order admission: first blocked job stalls the queue
BF_FIRSTFIT = 1   # skip blocked jobs, keep admitting anything that fits
BF_EASY = 2       # EASY: reservation for the head job, conservative backfill

BACKFILL_NAMES = {"none": BF_NONE, "first-fit": BF_FIRSTFIT, "firstfit": BF_FIRSTFIT,
                  "easy": BF_EASY}

INF = jnp.float32(jnp.inf)


def _register(cls):
    """Register a dataclass as a pytree (all fields are children)."""
    fields = [f.name for f in dataclasses.fields(cls)]
    jax.tree_util.register_dataclass(cls, data_fields=fields, meta_fields=[])
    return cls


# ---------------------------------------------------------------------------
# Static job table (inputs to the simulation; never mutated by the engine).
# ---------------------------------------------------------------------------
@_register
@dataclass
class JobTable:
    """Fixed-size (padded) job table. Shapes: [J] unless noted.

    Times are absolute seconds (float32) relative to the dataset origin.
    ``power_prof``/``util_prof`` are per-node traces sampled at
    ``SystemConfig.prof_dt``; scalar-only datasets (Fugaku, Lassen, Adastra)
    use P == 1. Missing samples are handled with last-observation-carried-
    forward by clamping the profile index (paper §3.2.2).
    """
    submit: jnp.ndarray        # f32[J] submit time
    limit: jnp.ndarray         # f32[J] requested walltime (s)
    wall: jnp.ndarray          # f32[J] actual runtime (s) -- ground truth
    nodes: jnp.ndarray         # i32[J] requested node count
    priority: jnp.ndarray      # f32[J] dataset-provided priority (higher = better)
    account: jnp.ndarray       # i32[J] issuing account id
    rec_start: jnp.ndarray     # f32[J] recorded start time (replay mode)
    first_node: jnp.ndarray    # i32[J] recorded first node of contiguous placement
    score: jnp.ndarray         # f32[J] ML / external score (higher = better)
    power_prof: jnp.ndarray    # f32[J, P] per-node power trace (W)
    util_prof: jnp.ndarray     # f32[J, P] utilization trace in [0, 1]
    valid: jnp.ndarray         # bool[J] padding mask
    # ML scoring basis (paper §4.4.2): exp(1/sqrt(X+1)) of the per-job
    # feature matrix, so the ranking score is *linear* in the alpha vector
    # (score = ml_basis @ alpha). The basis lives in the broadcast table
    # while alpha rides the traced Scenario axis — which is what lets a
    # whole ES population of alphas evaluate as ONE batched sweep
    # (repro.ml.train). ``None`` = no parameterized scoring (legacy
    # ``score`` column only).
    ml_basis: jnp.ndarray | None = None  # f32[J, K] or None
    # Measured per-node power replay (repro.traces, paper contribution 2):
    # recorded telemetry sampled at ``SystemConfig.prof_dt``, gathered by
    # the scan *instead of* evaluating the ``power_prof`` model whenever a
    # job's row carries a measurement — a negative sample is the
    # "no measurement" sentinel, so profile-less jobs (and padded rows,
    # filled with -1) fall back to the model bit-for-bit. ``None`` =
    # replay mode off, the compile-time fast path: the gather vanishes
    # and the graph is bit-identical to the pre-traces engine.
    power_profile: jnp.ndarray | None = None  # f32[J, Q] measured W, or None

    @property
    def num_jobs(self) -> int:
        return self.submit.shape[0]

    @property
    def prof_len(self) -> int:
        return self.power_prof.shape[1]


# ---------------------------------------------------------------------------
# Ledgers updated by the engine.
# ---------------------------------------------------------------------------
@_register
@dataclass
class AccountStats:
    """Per-account accumulators (paper §3.2.6 + §4.3). Shapes: [A]."""
    jobs_done: jnp.ndarray     # f32[A]
    node_hours: jnp.ndarray    # f32[A]
    energy: jnp.ndarray        # f32[A] Joules
    edp: jnp.ndarray           # f32[A] sum of E_job * turnaround
    ed2p: jnp.ndarray          # f32[A] sum of E_job * turnaround^2
    wait_sum: jnp.ndarray      # f32[A]
    turnaround_sum: jnp.ndarray  # f32[A]
    power_sum: jnp.ndarray     # f32[A] sum over jobs of avg per-node power
    fugaku_pts: jnp.ndarray    # f32[A]
    carbon_kg: jnp.ndarray     # f32[A] grid-signal-weighted emissions (kg CO2)
    cost: jnp.ndarray          # f32[A] electricity cost at the grid price ($)

    @staticmethod
    def zeros(num_accounts: int) -> "AccountStats":
        # one fresh buffer per field, NOT one shared array: the ledgers
        # ride the scan carry, which the engine runners donate — XLA
        # rejects donating the same buffer at two argument positions
        n = len(dataclasses.fields(AccountStats))
        return AccountStats(*(jnp.zeros((num_accounts,), jnp.float32)
                              for _ in range(n)))


@_register
@dataclass
class CoolingState:
    """Transient thermo-fluid state of the cooling plant (repro.cooling
    .model), hierarchical: halls -> CDU groups -> nodes.

    G = number of CDU groups, H = number of halls
    (``CoolingConfig.topology``); each hall owns a tower loop (basin +
    fan cells) serving its contiguous span of CDU groups. All
    temperatures in °C, flow in kg/s, fan staging in "active cells"
    (continuous in [0, cells installed in that hall]). A flat plant is
    H = 1.
    """
    t_supply: jnp.ndarray    # f32[G] CDU supply water temperature (°C)
    t_return: jnp.ndarray    # f32[G] CDU return water temperature (°C)
    mdot: jnp.ndarray        # f32[G] CDU water mass flow (kg/s, valve state)
    t_basin: jnp.ndarray     # f32[H] per-hall tower basin temperature (°C)
    fan_stages: jnp.ndarray  # f32[H] active tower cells per hall


@_register
@dataclass
class EventState:
    """Stochastic failure-process state (repro.events). Rides the scan
    carry only when the event layer is enabled (``events=`` on the engine
    runners); ``SimState.events is None`` is the compile-time "no events"
    fast path and keeps the pre-events graphs bit-identical.

    ``*_down_until`` hold the absolute sim time (s) each entity's repair
    completes: an entity is down while ``t < down_until`` — monotone time
    means a failed entity can never resurrect before its repair draw.
    N = nodes, G = CDU groups, C = installed tower cells.
    """
    node_down_until: jnp.ndarray   # f32[N] repair-complete time per node
    group_down_until: jnp.ndarray  # f32[G] repair-complete time per CDU group
    cell_down_until: jnp.ndarray   # f32[C] repair-complete time per tower cell
    # ride-through accumulators
    jobs_killed: jnp.ndarray       # f32[] jobs killed by failures
    jobs_requeued: jnp.ndarray     # f32[] killed jobs returned to the queue
    energy_lost_j: jnp.ndarray     # f32[] energy of killed jobs (not served)
    node_downtime_s: jnp.ndarray   # f32[] integral of down nodes x dt


@_register
@dataclass
class SimState:
    """Full engine state threaded through ``lax.scan``."""
    t: jnp.ndarray          # f32[] current simulation time (s)
    step: jnp.ndarray       # i32[] engine step index (grid-signal cursor)
    jstate: jnp.ndarray     # i32[J] job lifecycle state
    start: jnp.ndarray      # f32[J] realized start time (or +inf)
    end: jnp.ndarray        # f32[J] realized end time (or +inf)
    progress: jnp.ndarray   # f32[J] work-time since start (c*dt per step;
                            # == wall-clock elapsed when never throttled)
    jenergy: jnp.ndarray    # f32[J] accumulated job energy (J)
    node_job: jnp.ndarray   # i32[N] job id occupying each node, -1 when free
    # f32[N] end time of the job occupying each node, written where a job
    # gains nodes with the same f32 value as its ``end`` (never read on a
    # free or down node), so release is an elementwise ``t >= node_end``.
    # On the grid path DVFS stretches ``end`` after placement and this is
    # only a lower bound: that path writes it but releases by a gather.
    node_end: jnp.ndarray
    free_count: jnp.ndarray  # i32[] number of free nodes
    # i32[G, J] nodes each job holds in each CDU group (the node map's
    # per-group summary, written where a job is placed and never cleared:
    # a column is read only while its job is RUNNING, and a job leaves
    # RUNNING exactly when its nodes are freed). Group-major so the job
    # axis lies on the lanes. Power and heat per CDU come from it
    # (repro.power.model.group_power) without forming per-node power.
    job_group_nodes: jnp.ndarray
    accounts: AccountStats
    cooling: CoolingState
    # global accumulators
    energy_total: jnp.ndarray   # f32[] integral of facility input power (J)
    energy_it: jnp.ndarray      # f32[] integral of IT power (J)
    energy_loss: jnp.ndarray    # f32[] integral of conversion losses (J)
    completed: jnp.ndarray      # f32[] jobs completed inside the window
    emissions_kg: jnp.ndarray   # f32[] integral of facility power x carbon
    energy_cost: jnp.ndarray    # f32[] integral of facility power x price ($)
    energy_cooling: jnp.ndarray  # f32[] integral of cooling parasitics (J)
    heat_reuse_j: jnp.ndarray   # f32[] integral of exported (reused) heat (J)
    # stochastic failure-process state (repro.events); ``None`` =
    # compile-time "no event layer" (an empty pytree subtree, so every
    # existing runner/snapshot/stack path is untouched)
    events: EventState | None = None


@_register
@dataclass
class StepRecord:
    """One telemetry row per engine step (the ``ys`` of the scan)."""
    t: jnp.ndarray            # f32[]
    power_it: jnp.ndarray     # f32[] IT power (W)
    power_loss: jnp.ndarray   # f32[] rectifier+sivoc losses (W)
    power_cooling: jnp.ndarray  # f32[] cooling (tower fan + pumps) power (W)
    power_total: jnp.ndarray  # f32[] facility input power (W)
    pue: jnp.ndarray          # f32[]
    t_tower_return: jnp.ndarray  # f32[] water temp arriving at cooling towers
    util: jnp.ndarray         # f32[] busy nodes / total nodes
    n_queued: jnp.ndarray     # f32[]
    n_running: jnp.ndarray    # f32[]
    emissions_kg: jnp.ndarray   # f32[] CO2 emitted this step (kg)
    energy_cost: jnp.ndarray    # f32[] electricity cost this step ($)
    cap_w: jnp.ndarray          # f32[] active facility IT power cap (W)
    throttle_frac: jnp.ndarray  # f32[] 1 - DVFS cap factor (0 = unthrottled)
    # cooling-loop telemetry (repro.cooling.model)
    power_fan: jnp.ndarray      # f32[] tower fan power (W)
    power_pump: jnp.ndarray     # f32[] CDU pump power (W)
    q_reuse_w: jnp.ndarray      # f32[] heat exported for reuse (W)
    t_basin: jnp.ndarray        # f32[] tower basin temperature (°C)
    t_supply_max: jnp.ndarray   # f32[] hottest CDU supply temperature (°C)
    t_wetbulb: jnp.ndarray      # f32[] ambient wet-bulb driving the tower (°C)
    thermal_throttled: jnp.ndarray  # f32[] 1 when supply-temp admission gate on
    # per-hall telemetry (repro.systems.config.FacilityTopology; H = halls).
    # The scalar rows above stay facility aggregates — max / flow-weighted
    # mix over halls — so flat-plant (H = 1) series are unchanged.
    power_it_hall: jnp.ndarray      # f32[H] IT power landing in each hall (W)
    t_basin_hall: jnp.ndarray       # f32[H] per-hall basin temperature (°C)
    t_supply_max_hall: jnp.ndarray  # f32[H] hottest CDU supply per hall (°C)
    t_wetbulb_hall: jnp.ndarray     # f32[H] per-hall ambient wet-bulb (°C)
    cells_online: jnp.ndarray       # f32[H] tower cells available per hall
    # failure / ride-through telemetry (repro.events; zeros when the event
    # layer is off)
    nodes_down: jnp.ndarray         # f32[] nodes unavailable this step
    n_killed: jnp.ndarray           # f32[] jobs killed by failures this step
    overheat_hall: jnp.ndarray      # f32[H] per-hall setpoint-lost flag


# ---------------------------------------------------------------------------
# Per-run scenario parameters (traced; sweep them with vmap).
# ---------------------------------------------------------------------------
@_register
@dataclass
class Scenario:
    """Traced what-if knobs. Every knob after policy/backfill has a
    *neutral default*, so call sites construct Scenarios by keyword and
    adding a knob can never silently shift the meaning of an existing
    positional argument. ``Scenario.make`` converts to traced jnp leaves;
    raw-float construction (as used by ``engine.simulate_static``) keeps
    the values compile-time static."""
    policy: jnp.ndarray       # i32[] POLICY_*
    backfill: jnp.ndarray     # i32[] BF_*
    # weight applied to the account-derived key when mixing with base priority
    acct_weight: jnp.ndarray = 1.0   # f32[]
    # grid-aware knobs (repro.grid): deferral weights for the carbon/price
    # policies, and a multiplier on the facility power-cap schedule so a
    # single vmapped sweep can scan cap levels against one shared signal set.
    carbon_weight: jnp.ndarray = 1.0  # f32[] POLICY_CARBON deferral strength
    price_weight: jnp.ndarray = 1.0   # f32[] POLICY_PRICE deferral strength
    cap_scale: jnp.ndarray = 1.0      # f32[] scales GridSignals.cap_w
    # cooling-aware knobs (repro.cooling): deferral weight for the
    # thermal_aware policy, and an offset on the CDU supply setpoint so a
    # single vmapped sweep can scan setpoints against one compiled program.
    thermal_weight: jnp.ndarray = 1.0    # f32[] POLICY_THERMAL strength
    setpoint_delta_c: jnp.ndarray = 0.0  # f32[] offset on setpoint (°C)
    # maintenance what-if (repro.cooling + FacilityTopology): tower cells
    # taken offline. A scalar applies to every hall; a length-H vector
    # degrades halls individually (all scenarios in one sweep must agree
    # on the shape so the leaves stack).
    cells_offline: jnp.ndarray = 0.0     # f32[] or f32[H] cells offline
    # ML scoring coefficients (repro.ml.scoring): the POLICY_ML key is
    # -(table.score + table.ml_basis @ alpha), so a sweep can carry one
    # alpha vector *per scenario* — the ES training loop (repro.ml.train)
    # puts its whole population here. The scalar 0.0 default is neutral
    # (pure ``table.score`` ranking, the pre-training behavior).
    alpha: jnp.ndarray = 0.0             # f32[] or f32[K] scoring weights
    # stochastic failure knobs (repro.events; active only when the engine
    # runs with an ``events=EventConfig(...)``). Rates are hazards in
    # 1/s (0 = never fails); every knob is finite so scenario deltas ride
    # the serve wire as plain JSON numbers.
    failure_seed: jnp.ndarray = 0.0      # f32[] seed of the failure draws
    node_fail_rate: jnp.ndarray = 0.0    # f32[] per-node failure hazard (1/s)
    cdu_fail_rate: jnp.ndarray = 0.0     # f32[] per-CDU-group hazard (1/s)
    cell_fail_rate: jnp.ndarray = 0.0    # f32[] per-tower-cell hazard (1/s)
    # correlated common-cause fraction: probability scale of a *hall-wide*
    # CDU outage relative to the single-group hazard (0 = independent)
    failure_corr: jnp.ndarray = 0.0      # f32[] in [0, 1]
    repair_s: jnp.ndarray = 3600.0       # f32[] mean repair time (s)
    # grid demand-response event (cap step with a notice window); sentinel
    # values instead of inf: announce < 0 = no event, cap <= 0 = no cap
    dr_announce_s: jnp.ndarray = -1.0    # f32[] announcement time (s; <0 off)
    dr_notice_s: jnp.ndarray = 0.0       # f32[] notice window before the cap
    dr_duration_s: jnp.ndarray = 0.0     # f32[] how long the cap holds (s)
    dr_cap_w: jnp.ndarray = 0.0          # f32[] cap level during the event (W)

    @staticmethod
    @obs_timing.span("engine.scenarios")
    def make(policy: str | int, backfill: str | int = "none",
             acct_weight: float = 1.0, carbon_weight: float = 1.0,
             price_weight: float = 1.0, cap_scale: float = 1.0,
             thermal_weight: float = 1.0,
             setpoint_delta_c: float = 0.0,
             cells_offline=0.0, alpha=0.0,
             failure_seed: float = 0.0, node_fail_rate: float = 0.0,
             cdu_fail_rate: float = 0.0, cell_fail_rate: float = 0.0,
             failure_corr: float = 0.0, repair_s: float = 3600.0,
             dr_announce_s: float = -1.0, dr_notice_s: float = 0.0,
             dr_duration_s: float = 0.0,
             dr_cap_w: float = 0.0) -> "Scenario":
        p = POLICY_NAMES[policy] if isinstance(policy, str) else policy
        b = BACKFILL_NAMES[backfill] if isinstance(backfill, str) else backfill
        return Scenario(
            policy=jnp.int32(p), backfill=jnp.int32(b),
            acct_weight=jnp.float32(acct_weight),
            carbon_weight=jnp.float32(carbon_weight),
            price_weight=jnp.float32(price_weight),
            cap_scale=jnp.float32(cap_scale),
            thermal_weight=jnp.float32(thermal_weight),
            setpoint_delta_c=jnp.float32(setpoint_delta_c),
            cells_offline=jnp.asarray(cells_offline, jnp.float32),
            alpha=jnp.asarray(alpha, jnp.float32),
            failure_seed=jnp.float32(failure_seed),
            node_fail_rate=jnp.float32(node_fail_rate),
            cdu_fail_rate=jnp.float32(cdu_fail_rate),
            cell_fail_rate=jnp.float32(cell_fail_rate),
            failure_corr=jnp.float32(failure_corr),
            repair_s=jnp.float32(repair_s),
            dr_announce_s=jnp.float32(dr_announce_s),
            dr_notice_s=jnp.float32(dr_notice_s),
            dr_duration_s=jnp.float32(dr_duration_s),
            dr_cap_w=jnp.float32(dr_cap_w))


@obs_timing.span("engine.scenarios")
def stack_scenarios(scens: list) -> "Scenario":
    """Stack a list of Scenario leaves for vmapped sweeps. Leaves are
    broadcast to a common shape first, so scenarios that keep a vector
    knob at its scalar default (e.g. ``cells_offline=0.0``) stack against
    scenarios that set it per hall."""
    def stack(*xs):
        shape = jnp.broadcast_shapes(*(jnp.shape(x) for x in xs))
        return jnp.stack([jnp.broadcast_to(x, shape) for x in xs])
    return jax.tree_util.tree_map(stack, *scens)
