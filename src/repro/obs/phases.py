"""The engine step's phases, and the device time each one takes.

``engine_step`` (``repro.core.engine``) wraps each phase in a
``jax.named_scope`` of one of the names below. A scope is metadata only:
it lands in the ``op_name`` of every HLO instruction the phase produces
and changes nothing the compiler does. So the compiled module's text
(``compiled.as_text()``) says which phase each instruction belongs to,
and a profiler trace's per-operation device time (keyed by instruction,
``%fusion.250 = ...``) sums to device time per phase.

    >>> from repro.obs import phases
    >>> text = '''
    ... ENTRY %main (p: f32[8]) -> f32[8] {
    ...   %p = f32[8]{0} parameter(0)
    ...   ROOT %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f, metadata={op_name="jit(run)/while/body/engine.power/mul"}
    ... }
    ... '''
    >>> phases.phase_seconds({"%fusion.3 = f32[8] fusion(f32[8])": 2.0,
    ...                       "%copy.1 = f32[8] copy(f32[8])": 1.0}, text)
    {'engine.power': 2.0}
"""
from __future__ import annotations

import collections
import functools
import re

PREPARE = "engine.prepare"        # completions, node release, account fold,
#                                   arrivals
FAILURES = "engine.failures"      # failure/repair draws, demand response
QUEUE_ORDER = "engine.queue_order"  # thermal signal, policy keys, lexsort,
#                                   release-profile argsort, hall plan
ADMISSION = "engine.admission"    # the admission fori_loop and placement
POWER = "engine.power"            # job and node power, the cap-aware
#                                   baseline, DVFS cap, conversion losses
COOLING = "engine.cooling"        # node -> CDU -> hall sums and the plant
TELEMETRY = "engine.telemetry"    # StepRecord, ledgers, energy sums
PHASES = (PREPARE, FAILURES, QUEUE_ORDER, ADMISSION, POWER, COOLING,
          TELEMETRY)
OTHER = "other"

# Instructions whose device time spans other instructions' (their body's
# operations, which a trace lists as well).
CONTAINERS = frozenset({"while", "conditional", "call"})

# ``container``: the container instruction whose body holds this one;
# ``depth``: the loops around it (0 outside the scan over steps)
Instruction = collections.namedtuple(
    "Instruction", "name opcode op_name phase computation container depth")

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PHASE = re.compile(r"engine\.[a-z_]+")
_CALLED = re.compile(r"\b(body|condition|true_computation|false_computation"
                     r"|to_apply)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_FUSED = re.compile(r"\bcalls=%([\w.\-]+)")


def opcode(rest: str) -> str:
    """The opcode of an instruction from the text after its ``=``:
    ``f32[8]{0} fusion(...)`` or ``(s32[], f32[8]) while(...)``."""
    rest = rest.lstrip()
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return rest.lstrip().split("(", 1)[0].strip()


def phase_of(op_name: str) -> str:
    """The innermost engine phase named in an ``op_name`` path."""
    found = [p for p in _PHASE.findall(op_name) if p in PHASES]
    return found[-1] if found else OTHER


@functools.lru_cache(maxsize=4)
def instructions(text: str) -> tuple:
    """Every instruction the device runs as an operation of its own: those
    of the entry computation and of the loop bodies, conditions,
    branches and calls it reaches (not the insides of fusions, reducers
    or comparators). ``Instruction`` tuples.

    An instruction's phase is the innermost one its ``op_name`` names.
    The compiler makes some without one (layout copies, a cumulative
    sum rewritten as a ``reduce-window``, fusions it builds): a fusion
    takes the phase its fused computation names, and any other takes
    the phase of the loop it runs in when that loop is a phase's own (the
    admission loop); at the level of the scan over steps it stays
    ``OTHER``."""
    comps: dict = {}
    entry = None
    name = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(2)
            comps[name] = []
            if m.group(1):
                entry = name
            continue
        m = _INSTRUCTION.match(line)
        if m and name is not None:
            op = _OP_NAME.search(m.group(2))
            comps[name].append((m.group(1), m.group(2),
                                op.group(1) if op else ""))
    if entry is None:
        return ()
    # the phase each computation names last (a fused computation's root)
    named = {}
    for comp, body in comps.items():
        found = [phase_of(op) for _, _, op in body]
        found = [p for p in found if p != OTHER]
        if found:
            named[comp] = found[-1]
    out, seen = [], {entry}
    # computation, the container that runs it, phase it passes, loop depth
    todo = [(entry, None, None, 0)]
    while todo:
        comp, container, inherited, depth = todo.pop()
        for iname, rest, op_name in comps.get(comp, ()):
            code = opcode(rest)
            phase = phase_of(op_name)
            if phase == OTHER:
                fused = _FUSED.search(rest) if code == "fusion" else None
                phase = ((named.get(fused.group(1)) if fused else None)
                         or inherited or OTHER)
            called = [c for kind, c in _CALLED.findall(rest)
                      if kind != "to_apply" or code == "call"]
            for branches in _BRANCHES.findall(rest):
                called += [b.strip().lstrip("%") for b in branches.split(",")]
            # a loop inside the scan's body belongs to its phase
            passes = phase if depth > 0 and phase != OTHER else inherited
            for c in called:
                if c not in seen:
                    seen.add(c)
                    todo.append((c, iname, passes,
                                 depth + (code == "while")))
            out.append(Instruction(iname, code, op_name, phase, comp,
                                   container, depth))
    return tuple(out)


def phase_seconds(op_s: dict, text: str) -> dict:
    """Device seconds per phase (``OTHER`` for instructions outside every
    phase) from ``op_s``: seconds per operation, keyed by the trace's
    operation name (``%fusion.250 = pred[153600] fusion(...)``, with or
    without layouts and operands). An operation that is not an
    instruction of ``text`` with the same opcode belongs to another
    program and is left out.

    Each second is counted once. A container's time spans its body's
    operations, which the trace lists as well: only what they leave
    uncovered (the loop's own control and the device's waits inside it)
    counts, for the container's phase. The scan over steps and what wraps
    it count nowhere: their time is their body's."""
    by_name = {i.name: i for i in instructions(text)}
    seconds: dict = {}
    for key, s in op_s.items():
        head, _, rest = key.partition(" = ")
        name = head.strip().lstrip("%")
        ins = by_name.get(name)
        if ins is None or (rest and opcode(rest) != ins.opcode):
            continue
        seconds[name] = seconds.get(name, 0.0) + s
    inside: dict = {}
    for name, s in seconds.items():
        container = by_name[name].container
        inside[container] = inside.get(container, 0.0) + s
    out: dict = {}
    for name, s in seconds.items():
        ins = by_name[name]
        if ins.opcode in CONTAINERS:
            if ins.depth == 0:          # the scan, and what wraps it
                continue
            s = max(s - inside.get(name, 0.0), 0.0)
        out[ins.phase] = out.get(ins.phase, 0.0) + s
    return out
