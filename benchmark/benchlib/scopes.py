"""Attribute a profiler trace's device time to the program's named scopes,
and split its idle time into gaps inside a program and gaps between
programs.

* On the first ``/device:TPU:<k>`` plane, the line ``XLA Modules`` holds one
  event per program execution, named ``<module>(<fingerprint>)``, and the
  line ``XLA Ops`` one event per operation, named ``%<instruction> = ...``.
* The compiled HLO text of each program (``compiled.as_text()``) gives each
  instruction its ``op_name``: the ``jax.named_scope`` path it was traced
  under, such as ``jit(posv)/posv/potrf/factor/cholesky``.  Instruction
  names are unique within a module.
* An instruction's scope is the innermost ``<driver>/<phase>`` on that path
  among the configuration's drivers and their phases (``potrf/factor``;
  ``drivers`` maps each driver to ``{phase: role}``, see :func:`readings`
  and ``configs/<config>.json``'s ``scopes``).  One without a scope
  of its own (an operation a compiler pass made, a relayout of an
  argument) takes the most common scope of its operands, else of its users,
  else that of the instruction that calls its computation (a loop body's
  operations take the loop's).  A fusion has its own ``op_name``, which the
  compiler copies from one of its roots.
* Each moment of device time inside the traced window goes to exactly one
  group: the scope of the operation that began last among those running
  (a loop's body operations own their moments, the loop the rest); else
  ``(unscoped)``, for an operation of a program whose text was given; else
  ``(program <module>)``.  So the groups sum to the busy time of
  ``trace.reduce_trace``.
"""

from __future__ import annotations

import os
import re
from collections import defaultdict

from .trace import (DEVICE_PLANE, OPS_LINE, find_xplane, host_timeline,
                    idle_gaps, union_length)

MODULES_LINE = "XLA Modules"
UNSCOPED = "(unscoped)"

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_EVENT = re.compile(r"^%?([\w.\-]+) = ")


def scope_of(op_name: str, drivers: dict):
    """The innermost ``<driver>/<phase>`` on an ``op_name`` path whose
    driver and phase ``drivers`` names, or None."""
    parts = op_name.split("/")
    for i in range(len(parts) - 2, -1, -1):
        if parts[i + 1] in drivers.get(parts[i], ()):
            return f"{parts[i]}/{parts[i + 1]}"
    return None


def module_name(text: str) -> str:
    return _MODULE.search(text).group(1)


def instruction_scopes(text: str, drivers: dict) -> dict:
    """``{instruction: scope or None}`` for every instruction of one
    module's HLO text."""
    own, caller = {}, {}
    comps, lines = set(), []
    comp = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            comps.add(comp)
            continue
        m = _INSTRUCTION.match(line)
        if m and comp is not None:
            lines.append((comp, m.group(1), line))
    home, operands = {}, {}
    for comp, name, line in lines:
        home[name] = comp
        op = _OP_NAME.search(line)
        own[name] = scope_of(op.group(1), drivers) if op else None
        refs = _REF.findall(line[line.index("=") + 1:])
        operands[name] = [r for r in refs if r in home and home[r] == comp]
        for ref in refs:
            if ref in comps and ref != comp:
                caller.setdefault(ref, name)
    # a module's text lists each computation's instructions after their
    # operands: operands in that order, then users in the reverse order
    users = defaultdict(list)
    for name, refs in operands.items():
        for r in refs:
            users[r].append(name)
    for order, nearby in ((list(own), operands),
                          (list(own)[::-1], users)):
        for name in order:
            if own[name] is None:
                found = [own[r] for r in nearby[name] if own[r] is not None]
                if found:
                    own[name] = max(found, key=found.count)
    out = {}

    def resolve(name, seen=()):
        if name in out:
            return out[name]
        s = own[name]
        up = caller.get(home[name])
        if s is None and up is not None and up not in seen:
            s = resolve(up, seen + (name,))
        out[name] = s
        return s

    for name in own:
        resolve(name)
    return out


def reduce_scopes(path: str, texts, drivers: dict,
                  window_name: str = "bench.window") -> dict:
    """Reduce the trace at ``path`` (a file or a profiler log directory)
    against the HLO ``texts`` of the programs the window runs, by the
    scopes of ``drivers``.

    Returns ``busy_s``, ``window_s``, ``groups`` (``{group: seconds}``, see
    the module's doc), ``in_program_s`` (idle time inside a program's
    execution), ``between_programs_s`` (idle time outside every execution)
    and ``programs`` (``[[module, executions, seconds], ...]``, executions
    that begin in the window, the longest first)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    scopes = {}
    for t in texts:
        scopes[module_name(t)] = instruction_scopes(t, drivers)
    pd = ProfileData.from_file(path)
    ops, modules, window = [], [], None
    device_seen = False
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name) and not device_seen:
            device_seen = True
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dest = ops if line.name == OPS_LINE else modules
                for ev in line.events:
                    if ev.duration_ns > 0:
                        dest.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                     ev.name))
        elif plane.name.startswith("/host:") and window is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window_name and ev.duration_ns > 0:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if not ops:
        raise ValueError(f"{path}: no device operations in the trace")
    if window is None:
        window = (min(s for s, _, _ in ops), max(e for _, e, _ in ops))
    lo, hi = window
    modules.sort()

    # the program each operation ran in: the execution open at its start
    labelled = []
    k = 0
    for s, e, name in sorted(ops):
        while k + 1 < len(modules) and modules[k + 1][0] <= s:
            k += 1
        prog = None
        if modules and modules[k][0] <= s < modules[k][1]:
            prog = modules[k][2].split("(", 1)[0]
        labelled.append((s, e, _group(name, prog, scopes)))
    groups = defaultdict(float)
    for s, e, g in host_timeline(labelled):
        d = min(e, hi) - max(s, lo)
        if d > 0:
            groups[g] += d
    busy = union_length([(s, e) for s, e, _ in ops], lo, hi)
    gaps = idle_gaps([(s, e) for s, e, _ in ops], lo, hi)
    in_program = _overlap(gaps, [(s, e) for s, e, _ in modules])
    runs = defaultdict(lambda: [0, 0.0])
    for s, e, name in modules:
        if lo <= s < hi:
            r = runs[name.split("(", 1)[0]]
            r[0] += 1
            r[1] += (min(e, hi) - s) / 1e9
    return {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9,
            "groups": {g: v / 1e9 for g, v in groups.items()},
            "in_program_s": in_program / 1e9,
            "between_programs_s": (hi - lo - busy - in_program) / 1e9,
            "programs": sorted(([m, n, s] for m, (n, s) in runs.items()),
                               key=lambda r: -r[2])}


def _group(event: str, prog, scopes) -> str:
    if prog not in scopes:
        return f"(program {prog})"
    m = _EVENT.match(event)
    return (scopes[prog].get(m.group(1)) if m else None) or UNSCOPED


def _overlap(gaps, intervals) -> float:
    """Length of the part of the disjoint, sorted ``gaps`` that the union of
    ``intervals`` covers."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    total, j = 0.0, 0
    for s, e in gaps:
        while j < len(merged) and merged[j][1] <= s:
            j += 1
        i = j
        while i < len(merged) and merged[i][0] < e:
            total += min(e, merged[i][1]) - max(s, merged[i][0])
            i += 1
    return total


def breakdown(red: dict, top: int = 10) -> dict:
    """The reduction's groups and programs for a result line: ``scopes``
    (``[group, seconds]``, the longest first) and ``programs``."""
    groups = sorted(red["groups"].items(), key=lambda kv: -kv[1])[:top]
    return {"scopes": [[g, s] for g, s in groups],
            "programs": red["programs"][:top]}


def role_seconds(red: dict, drivers: dict) -> dict:
    """Device seconds of each role: the sum of its scope groups."""
    out = defaultdict(float)
    for g, s in red["groups"].items():
        driver, _, phase = g.partition("/")
        role = drivers.get(driver, {}).get(phase)
        if role:
            out[role] += s
    return out


def readings(red: dict, steps: int, drivers: dict, least_s: dict) -> dict:
    """The per-role numbers of a traced window of ``steps`` solves.

    ``drivers`` gives each ``<driver>/<phase>`` a role (``wrapper``,
    ``factor``, ``sweep``, ...) or none; ``least_s`` maps a role to the
    least time of the job it is measured against, once per step
    (``jobs.least_time_s``).  A roofline whose role took no device time is
    left out:

    * ``<role>_share``: the role's device time over busy time, in percent,
      for every role ``drivers`` names;
    * ``<role>_roofline``: the least time over the role's device time per
      step, in percent, for every role of ``least_s``;
    * ``host_gap_ms``: idle time between programs per step, in ms."""
    out = {"host_gap_ms": red["between_programs_s"] * 1e3 / steps}
    t = role_seconds(red, drivers)
    if red["busy_s"] > 0:
        for role in dict.fromkeys(r for phases in drivers.values()
                                  for r in phases.values() if r):
            out[f"{role}_share"] = 100.0 * t[role] / red["busy_s"]
    for role, least in least_s.items():
        if least and t[role] > 0:
            out[f"{role}_roofline"] = 100.0 * least / (t[role] / steps)
    return out
