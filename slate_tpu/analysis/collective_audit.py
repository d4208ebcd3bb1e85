"""Tier B: compile-time collective race auditor.

``obs/costaudit.py`` *counts* collectives; this module *orders* them.  For a
compiled SPMD executable it extracts the per-participant collective sequence
— op kind, channel id, replica groups, and the call context (while body /
conditional branch) each site sits in — and statically verifies schedule
consistency:

* **coverage** — every replica group names valid participants and no device
  appears twice in one collective's groups (a duplicated id deadlocks the
  rendezvous);
* **channel discipline** — in a cross-module (MPMD) program no two distinct
  collective instructions share a channel id (interleaved channel reuse is
  how mismatched schedules corrupt each other's payloads).  An SPMD-
  partitioned module (header ``num_partitions`` > 1) is exempt: XLA 0.9
  stamps ``channel_id=1`` on every cross-partition collective as a mode
  marker, and every partition runs the one program, so the id pairs
  nothing.  Every program JAX compiles for this library is such a module:
  the check applies to none of them, only to hand-written MPMD modules
  (tests/test_analysis.py's synthetic channel-reuse fixture).  On library
  programs a corrupted schedule is caught by cross-participant agreement;
* **uniform control flow** — no collective reachable only under a
  ``conditional`` branch (a ``lax.cond`` whose predicate diverges across
  participants leaves part of the mesh waiting at a rendezvous the rest
  never reaches: the classic distributed deadlock, caught at compile time);
* **cross-participant agreement** — projecting the schedule onto each
  participant, every pair of devices must see their *joint* collectives in
  the same order (:func:`verify_participant_schedules` — the check the
  corruption test in tests/test_analysis.py drives directly).

Everything runs on the virtual CPU mesh (``jit(...).lower(...).compile()``,
nothing executes), so the audit gates in CI with zero TPU time — the same
discipline as ``obs/scaling.py``, whose :func:`~slate_tpu.obs.scaling.specs`
registry supplies the audited routines.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from ..obs.costaudit import (COLLECTIVE_OPS, Instr, module_num_partitions,
                             parse_computations)


@dataclasses.dataclass(frozen=True)
class CollectiveEvent:
    """One collective site in schedule order, with its call context."""

    op: str                                   #: base opcode (``-start`` folded)
    name: str                                 #: HLO instruction name
    computation: str                          #: owning computation
    channel_id: Optional[int]
    groups: Tuple[Tuple[int, ...], ...]       #: () = all devices, one group
    branch_path: Tuple[Tuple[str, int], ...]  #: (cond instr, branch idx) chain
    while_depth: int                          #: enclosing while-loop nesting
    #: True when every enclosing conditional's predicate is *proven* uniform
    #: across participants (derived from full-mesh collectives/constants
    #: only) — such a branch collective cannot strand part of the mesh
    cond_uniform: bool = False
    #: True when an enclosing ``while``'s trip count can differ across the
    #: mesh: its condition reads a per-device divergence seed (partition-id/
    #: replica-id/rng/infeed/recv) directly, or reads a carry element whose
    #: body update chain is tainted by one — either way a body collective
    #: runs a different number of rendezvous on different devices
    while_divergent: bool = False
    #: ``source_target_pairs`` for collective-permute (None otherwise):
    #: direction matters at the rendezvous, so it participates in identity
    pairs: Optional[Tuple[Tuple[int, int], ...]] = None
    #: True when the owning module is SPMD-partitioned (``num_partitions``
    #: > 1): its channel id is a cross-partition marker, not a pairing key
    spmd: bool = False

    def participants(self, nproc: int) -> Tuple[int, ...]:
        if not self.groups:
            return tuple(range(nproc))
        out = sorted({d for g in self.groups for d in g})
        return tuple(out)

    def key(self) -> Tuple[str, Tuple[Tuple[int, ...], ...],
                           Optional[Tuple[Tuple[int, int], ...]]]:
        """Identity used when comparing schedules across participants:
        the semantic rendezvous (opcode + replica groups + permute
        direction), *not* the HLO instruction name or channel id — those
        are compilation artifacts that legitimately differ between
        independently compiled modules (one extra local op shifts every
        later auto-assigned name/id), and the cross-schedule comparator
        must not flag renames as races.  ``pairs`` is included because a
        collective-permute's groups flatten its source_target_pairs into
        an unordered device set — two permutes with opposite directions
        share groups but mismatch at runtime."""
        return (self.op, self.groups, self.pairs)

    def describe(self) -> str:
        loc = self.computation
        if self.while_depth:
            loc += f" (while depth {self.while_depth})"
        if self.branch_path:
            loc += " (conditional branch " + "/".join(
                f"{c}#{i}" for c, i in self.branch_path) + \
                (", uniform predicate)" if self.cond_uniform else ")")
        groups = "all" if not self.groups else \
            ",".join("{" + ",".join(map(str, g)) + "}" for g in self.groups)
        pairs = "" if self.pairs is None else " pairs=" + \
            ",".join(f"{a}->{b}" for a, b in self.pairs)
        return (f"{self.op} %{self.name} channel={self.channel_id} "
                f"groups={groups}{pairs} in {loc}")


# opcodes whose *output* is uniform across the full mesh regardless of their
# inputs (the result of a full-group rendezvous is the same everywhere)
_UNIFORM_SOURCES = frozenset({"all-reduce", "all-gather",
                              "collective-broadcast"})
# opcodes whose output is intrinsically per-device (or not worth proving)
_NONUNIFORM_OPS = frozenset({"parameter", "partition-id", "replica-id",
                             "rng", "rng-bit-generator", "infeed", "recv",
                             "recv-done", "while", "conditional",
                             "collective-permute", "reduce-scatter",
                             "all-to-all"})


def _full_mesh(groups: Tuple[Tuple[int, ...], ...], nproc: int) -> bool:
    if not groups:
        return True                    # replica_groups={}: all devices
    return len(groups) == 1 and set(groups[0]) == set(range(nproc))


class _UniformityAnalysis:
    """Backward dataflow over one computation: is a value provably identical
    on every participant?

    A value is uniform when every path of its def chain bottoms out in a
    constant/iota or a *full-mesh* all-reduce/all-gather/broadcast (whose
    output is the same everywhere by construction); elementwise/structural
    ops and deterministic local kernels (fusions, custom-calls) propagate
    uniformity from their operands.  Per-device seeds — parameters
    (sharded inputs), partition/replica ids, permutes, scatters, loop
    carries — are conservatively non-uniform.  This is what lets the
    auditor pass CholQR's rank-deficiency fallback (predicate derived from
    the psum'd Gram matrix: uniform) while still flagging a lax.cond on a
    genuinely local value."""

    def __init__(self, comps: Dict[str, List[Instr]], nproc: int):
        self.comps = comps
        self.by_name = {cname: {i.name: i for i in instrs}
                        for cname, instrs in comps.items()}
        self.nproc = nproc
        self._memo: Dict[Tuple[str, str], bool] = {}
        self._comp_pure: Dict[str, bool] = {}

    def _computation_pure(self, cname: str) -> bool:
        """No per-device seed op anywhere inside (fusion-body scan)."""
        cached = self._comp_pure.get(cname)
        if cached is not None:
            return cached
        self._comp_pure[cname] = True      # break cycles optimistically
        ok = True
        for ins in self.comps.get(cname, ()):
            base = ins.base_opcode()
            if base in _NONUNIFORM_OPS and base != "parameter" \
                    or base in COLLECTIVE_OPS:
                ok = False
                break
            for names in ins.callees().values():
                for c in names:
                    if c != cname and not self._computation_pure(c):
                        ok = False
                        break
        self._comp_pure[cname] = ok
        return ok

    def uniform(self, cname: str, ref: str, depth: int = 0) -> bool:
        key = (cname, ref)
        if key in self._memo:
            return self._memo[key]
        if depth > 200:
            return False
        self._memo[key] = False            # conservative while in-flight
        ins = self.by_name.get(cname, {}).get(ref)
        if ins is None:
            return False                   # parameter / cross-computation
        base = ins.base_opcode()
        if base in _UNIFORM_SOURCES:
            rg = ins.replica_groups()
            out = _full_mesh(rg if rg is not None else (), self.nproc)
        elif base in _NONUNIFORM_OPS:
            out = False
        elif base in ("constant", "iota"):
            out = True
        else:
            # elementwise / structural / fusion / custom-call: propagate,
            # requiring any called computation to be free of per-device seeds
            out = all(self._computation_pure(c)
                      for names in ins.callees().values() for c in names) \
                and all(self.uniform(cname, r, depth + 1)
                        for r in ins.operand_refs())
        self._memo[key] = out
        return out


def extract_events(hlo_text: str,
                   nproc: Optional[int] = None) -> List[CollectiveEvent]:
    """Walk the compiled module from ENTRY in schedule order, expanding
    called computations (`while` bodies, `conditional` branches, fusions),
    and emit every collective site with its context — including whether the
    predicates guarding it are provably uniform.

    ``nproc`` is the mesh size the uniformity proof runs at.  Pass the real
    device count whenever you know it: inferring it from the module (header
    ``num_partitions``, else max participant seen) under-counts when every
    collective in the module is a subgroup one, and a subgroup rendezvous
    mistaken for full-mesh turns a genuinely divergent predicate into a
    false uniformity proof."""
    comps, entry = parse_computations(hlo_text)
    if entry is None:
        # fall back: modules without an ENTRY marker (shouldn't happen on
        # Compiled.as_text(), but the parser must not invent a schedule)
        entry = next(iter(comps), None)
    events: List[CollectiveEvent] = []
    if entry is None:
        return events
    if nproc is None:
        nproc = module_num_partitions(hlo_text) or _max_participant(comps) + 1
    uni = _UniformityAnalysis(comps, nproc)
    spmd = (module_num_partitions(hlo_text) or 1) > 1

    def walk(comp: str, branch_path: Tuple[Tuple[str, int], ...],
             while_depth: int, uniform_so_far: bool,
             seen: Tuple[str, ...], while_divergent: bool = False) -> None:
        if comp in seen:               # defensive: HLO computations form a DAG
            return
        for ins in comps.get(comp, ()):
            base = ins.base_opcode()
            if base in COLLECTIVE_OPS and not ins.opcode.endswith("-done"):
                pairs = ins.source_target_pairs()
                if pairs is not None:
                    groups: Tuple[Tuple[int, ...], ...] = (
                        tuple(sorted({d for p in pairs for d in p})),)
                else:
                    rg = ins.replica_groups()
                    groups = rg if rg is not None else ()
                events.append(CollectiveEvent(
                    op=base, name=ins.name, computation=comp,
                    channel_id=ins.channel_id(), groups=groups,
                    branch_path=branch_path, while_depth=while_depth,
                    cond_uniform=bool(branch_path) and uniform_so_far,
                    while_divergent=while_divergent, pairs=pairs,
                    spmd=spmd))
            callees = ins.callees()
            if ins.opcode == "while":
                div = while_divergent or \
                    _while_trip_count_divergent(comps, ins, nproc)
                for attr in ("condition", "body"):
                    for c in callees.get(attr, ()):
                        walk(c, branch_path, while_depth + 1,
                             uniform_so_far, seen + (comp,), div)
            elif ins.opcode == "conditional":
                refs = ins.operand_refs()
                pred_uniform = bool(refs) and uni.uniform(comp, refs[0])
                branches = callees.get("branch_computations") or \
                    [c for attr in ("true_computation", "false_computation")
                     for c in callees.get(attr, ())]
                for idx, c in enumerate(branches):
                    walk(c, branch_path + ((ins.name, idx),), while_depth,
                         uniform_so_far and pred_uniform, seen + (comp,),
                         while_divergent)
            else:
                for attr, names in callees.items():
                    if attr == "branch_computations":
                        continue
                    for c in names:
                        walk(c, branch_path, while_depth, uniform_so_far,
                             seen + (comp,), while_divergent)

    walk(entry, (), 0, True, ())
    return events


# ops whose value is intrinsically per-device: a while condition touching
# one (directly, or through a carry element whose body update is tainted by
# one) can give the mesh divergent trip counts.  Counter-driven carries stay
# clean — in an SPMD module they start and update identically everywhere —
# so :func:`_carry_taint` tracks taint per carry element instead of flagging
# every loop in the registry (the blocked eigensolver/iterative-refinement
# whiles are counter-driven and race-free, even where their *data* elements
# are computed with partition-id shard indexing).
_DIVERGENCE_SEEDS = frozenset({"partition-id", "replica-id", "rng",
                               "rng-bit-generator", "infeed", "recv"})

_INDEX_RE = re.compile(r"\bindex=(\d+)")


def _has_divergence_seed(comps: Dict[str, List[Instr]], cname: str,
                         _seen: Optional[set] = None) -> bool:
    """Does ``cname`` (transitively through its callees) contain an op from
    ``_DIVERGENCE_SEEDS``?"""
    seen = _seen if _seen is not None else set()
    if cname in seen:
        return False
    seen.add(cname)
    for ins in comps.get(cname, ()):
        base = ins.base_opcode()
        if base in _DIVERGENCE_SEEDS:
            return True
        for names in ins.callees().values():
            for c in names:
                if _has_divergence_seed(comps, c, seen):
                    return True
    return False


def _while_trip_count_divergent(comps: Dict[str, List[Instr]], ins: Instr,
                                nproc: int) -> bool:
    """Can this ``while``'s trip count differ across the mesh?

    True when the condition computation contains a divergence seed itself,
    or when it reads a carry element whose update chain in the body is
    tainted by one (the carry-laundering case: ``body`` folds partition-id
    into the counter, ``cond`` compares the counter against a constant —
    no seed ever appears in the condition, yet trip counts diverge)."""
    callees = ins.callees()
    conds = callees.get("condition", ())
    if any(_has_divergence_seed(comps, c) for c in conds):
        return True
    reads: Optional[Set[int]] = set()
    for c in conds:
        r = _condition_carry_reads(comps, c)
        if r is None:
            reads = None               # non-tuple carry / whole-tuple use
            break
        reads.update(r)
    if reads is not None and not reads:
        return False                   # condition reads no carry state at all
    for b in callees.get("body", ()):
        tainted = _carry_taint(comps, b, nproc)
        if tainted and (reads is None or reads & tainted):
            return True
    return False


def _condition_carry_reads(comps: Dict[str, List[Instr]], cname: str
                           ) -> Optional[Set[int]]:
    """Carry-tuple indices the condition computation reads through
    ``get-tuple-element`` on its parameter; None = conservatively all
    (non-tuple carry, or the parameter used whole)."""
    instrs = comps.get(cname, ())
    params = {i.name for i in instrs if i.opcode == "parameter"}
    reads: Set[int] = set()
    for ins in instrs:
        refs = ins.operand_refs()
        if ins.opcode == "get-tuple-element" and refs and refs[0] in params:
            m = _INDEX_RE.search(ins.tail)
            if m is None:
                return None
            reads.add(int(m.group(1)))
        elif any(r in params for r in refs):
            return None
    return reads


def _carry_taint(comps: Dict[str, List[Instr]], bname: str,
                 nproc: int) -> Set[int]:
    """Carry-tuple indices whose next-iteration value (the body's ROOT
    tuple element) is tainted by a divergence seed.

    Per-instruction dataflow: seeds taint; a *full-mesh*
    all-reduce/all-gather/broadcast launders taint (its output is identical
    everywhere no matter the inputs); everything else propagates taint from
    its operands and from seeds inside called computations (fusion bodies).
    ``get-tuple-element`` on the body parameter turns into a dependence on
    that carry index, resolved by fixpoint so taint flows across iterations
    (element k updated from tainted element j)."""
    instrs = comps.get(bname, ())
    if not instrs:
        return set()
    by_name = {i.name: i for i in instrs}
    params = {i.name for i in instrs if i.opcode == "parameter"}
    root = next((i for i in instrs if i.is_root), instrs[-1])
    elems = root.operand_refs() if root.opcode == "tuple" else [root.name]

    # ref -> (seed_tainted, carry indices depended on; None = whole carry)
    memo: Dict[str, Tuple[bool, Optional[Set[int]]]] = {}

    def deps(ref: str) -> Tuple[bool, Optional[Set[int]]]:
        if ref in memo:
            return memo[ref]
        memo[ref] = (False, set())     # in-flight (HLO is a DAG; defensive)
        ins2 = by_name.get(ref)
        if ins2 is None:
            out: Tuple[bool, Optional[Set[int]]] = (False, set())
        elif ins2.opcode == "parameter":
            out = (False, None)
        else:
            base = ins2.base_opcode()
            refs = ins2.operand_refs()
            if ins2.opcode == "get-tuple-element" and refs \
                    and refs[0] in params:
                m = _INDEX_RE.search(ins2.tail)
                out = (False, {int(m.group(1))} if m else None)
            elif base in _DIVERGENCE_SEEDS:
                out = (True, set())
            elif base in _UNIFORM_SOURCES:
                rg = ins2.replica_groups()
                out = (False, set()) if _full_mesh(
                    rg if rg is not None else (), nproc) \
                    else _merge(refs)
            else:
                seed = any(_has_divergence_seed(comps, c)
                           for names in ins2.callees().values()
                           for c in names)
                s, idxs = _merge(refs)
                out = (seed or s, idxs)
        memo[ref] = out
        return out

    def _merge(refs: List[str]) -> Tuple[bool, Optional[Set[int]]]:
        seed, idxs = False, set()
        for r in refs:
            s, i = deps(r)
            seed = seed or s
            if i is None or idxs is None:
                idxs = None
            else:
                idxs |= i
        return seed, idxs

    elem_deps = [deps(r) for r in elems]
    tainted = {k for k, (s, _) in enumerate(elem_deps) if s}
    changed = True
    while changed:
        changed = False
        for k, (_, idxs) in enumerate(elem_deps):
            if k in tainted:
                continue
            if (idxs is None and tainted) or (idxs and idxs & tainted):
                tainted.add(k)
                changed = True
    return tainted


def _max_participant(comps: Dict[str, List[Instr]]) -> int:
    top = 0
    for instrs in comps.values():
        for ins in instrs:
            rg = ins.replica_groups()
            for g in rg or ():
                top = max(top, max(g, default=0))
            for a, b in ins.source_target_pairs() or ():
                top = max(top, a, b)
    return top


def participant_schedules(events: Sequence[CollectiveEvent], nproc: int
                          ) -> Dict[int, List[CollectiveEvent]]:
    """Project the global schedule onto each participant: device ``d`` sees
    exactly the collectives whose groups include it.

    Projections of a *single* SPMD module are self-consistent by
    construction (every pair filters the same ordered list), so feed
    :func:`verify_participant_schedules` views from independent sources —
    separately compiled per-host modules, or a deliberately corrupted
    schedule as in the corruption test."""
    out: Dict[int, List[CollectiveEvent]] = {d: [] for d in range(nproc)}
    for ev in events:
        for d in ev.participants(nproc):
            if d in out:
                out[d].append(ev)
    return out


# ---------------------------------------------------------------------------
# checks


def verify_events(events: Sequence[CollectiveEvent], nproc: int) -> List[str]:
    """Structural checks on the global schedule (coverage, channels, control
    flow).  Returns findings; empty list = consistent."""
    findings: List[str] = []
    chan_sites: Dict[int, List[str]] = {}
    for ev in events:
        seen: Dict[int, int] = {}
        for g in ev.groups:
            for d in g:
                seen[d] = seen.get(d, 0) + 1
                if d >= nproc or d < 0:
                    findings.append(
                        f"{ev.describe()}: participant {d} outside the "
                        f"P={nproc} mesh")
        dups = sorted(d for d, c in seen.items() if c > 1)
        if dups:
            findings.append(
                f"{ev.describe()}: device(s) {dups} appear in more than one "
                "replica group of the same collective (rendezvous deadlock)")
        if ev.channel_id is not None and not ev.spmd:
            chan_sites.setdefault(ev.channel_id, []).append(
                f"%{ev.name}@{ev.computation}")
        if ev.branch_path and not ev.cond_uniform:
            findings.append(
                f"{ev.describe()}: collective reachable only under a "
                "conditional branch whose predicate is not provably uniform "
                "— a divergent lax.cond predicate strands part of the mesh "
                "at the rendezvous")
        if ev.while_depth and ev.while_divergent:
            findings.append(
                f"{ev.describe()}: collective inside a while loop whose "
                "condition reads a per-device value (partition-id/replica-"
                "id/rng/infeed/recv) — divergent trip counts run a "
                "different number of rendezvous on different devices")
    for chan, sites in sorted(chan_sites.items()):
        uniq = sorted(set(sites))
        if len(uniq) > 1:
            findings.append(
                f"channel {chan} reused by {len(uniq)} distinct collective "
                f"instructions: {', '.join(uniq)} (interleaved channel "
                "reuse corrupts rendezvous matching)")
    return findings


def verify_participant_schedules(
        schedules: Dict[int, List[CollectiveEvent]],
        nproc: Optional[int] = None) -> List[str]:
    """Cross-participant agreement: for every device pair (p, q), the
    subsequence of collectives involving *both* must be identical on both
    sides — same sites, same order.  A participant missing a psum the rest
    of its group executes (the corruption test's scenario) surfaces here.

    Only meaningful when the schedules come from *independent* sources
    (separately compiled per-host programs, replayed traces, corrupted
    fixtures): per-participant views projected from one SPMD module agree
    trivially, which is why :func:`audit_hlo` relies on
    :func:`verify_events` for its single-module guarantees."""
    nproc = nproc if nproc is not None else len(schedules)
    findings: List[str] = []
    devs = sorted(schedules)
    for i, p in enumerate(devs):
        for q in devs[i + 1:]:
            jp = [ev for ev in schedules[p]
                  if q in ev.participants(nproc)]
            jq = [ev for ev in schedules[q]
                  if p in ev.participants(nproc)]
            kp = [ev.key() for ev in jp]
            kq = [ev.key() for ev in jq]
            if kp == kq:
                continue
            # name the first divergence precisely
            k = 0
            while k < min(len(kp), len(kq)) and kp[k] == kq[k]:
                k += 1
            if k < len(kp) and k < len(kq):
                findings.append(
                    f"participants {p} and {q} disagree at joint collective "
                    f"#{k}: device {p} expects {jp[k].describe()} but device "
                    f"{q} expects {jq[k].describe()}")
            elif k < len(kp):
                findings.append(
                    f"participant {q} is missing joint collective #{k} that "
                    f"device {p} executes: {jp[k].describe()} — device {p} "
                    "blocks at a rendezvous the peer never reaches")
            else:
                findings.append(
                    f"participant {p} is missing joint collective #{k} that "
                    f"device {q} executes: {jq[k].describe()} — device {q} "
                    "blocks at a rendezvous the peer never reaches")
    return findings


def audit_hlo(hlo_text: str, nproc: int) -> Dict[str, Any]:
    """Full audit of one compiled module's HLO text.

    Runs the structural checks (:func:`verify_events`: group coverage,
    channel discipline, divergent-cond reachability).  The pairwise
    cross-schedule comparison is deliberately *not* run here — projections
    of one SPMD module agree by construction, so it would be a constant-
    empty check at O(P² · events) cost; use
    :func:`verify_participant_schedules` on independently sourced
    schedules instead."""
    events = extract_events(hlo_text, nproc)
    findings = verify_events(events, nproc)
    return {"collective_sites": len(events),
            "uniform_cond_sites": sum(
                1 for e in events if e.branch_path and e.cond_uniform),
            "schedule": [ev.describe() for ev in events],
            "findings": findings}


def audit_compiled(compiled, nproc: int) -> Dict[str, Any]:
    """Audit one ``jax.stages.Compiled`` executable."""
    try:
        hlo = compiled.as_text()
    # slate-lint: disable=SLT501 -- HLO rendering shim (same as costaudit's):
    # the failure is reported as an audit finding, nothing numerical runs here
    except Exception as e:
        return {"collective_sites": 0, "schedule": [],
                "findings": [f"could not render compiled HLO: "
                             f"{type(e).__name__}: {e}"]}
    return audit_hlo(hlo, nproc)


def audit_routines(pset: Sequence[int] = (2, 4, 8),
                   names: Optional[Sequence[str]] = None,
                   progress=None) -> List[Dict[str, Any]]:
    """Run the ordering audit over the obs/scaling routine registry — every
    AOT-audited distributed routine at each requested device count.

    Imports jax lazily (the AST tier must stay importable without it)."""
    from ..obs import scaling

    rows: List[Dict[str, Any]] = []
    wanted = set(names) if names else None
    if wanted is not None:
        unknown = sorted(wanted - {s.name for s in scaling.specs()})
        if unknown:
            # a typo must not read as "audited clean, 0 findings"
            raise ValueError(
                f"unknown routine name(s): {', '.join(unknown)} "
                f"(see obs.scaling.spec_names())")
    for nproc in pset:
        grid = scaling.make_grid(nproc)
        for spec in scaling.specs():
            if wanted is not None and spec.name not in wanted:
                continue
            row: Dict[str, Any] = {"routine": spec.name, "P": nproc,
                                   "module": spec.module}
            compiled, problem = scaling.compile_spec(spec, grid)
            if problem is not None:
                row.update(problem)
            else:
                row.update(audit_compiled(compiled, nproc))
            rows.append(row)
            if progress is not None:
                progress(row)
    return rows


def summarize(rows: Iterable[Dict[str, Any]]) -> Tuple[int, int, List[str]]:
    """(audited, total_findings, flattened finding lines) over audit rows."""
    audited = 0
    lines: List[str] = []
    for row in rows:
        if row.get("error") or row.get("skipped"):
            continue
        audited += 1
        for f in row.get("findings", ()):
            lines.append(f"P={row['P']} {row['routine']}: {f}")
    return audited, len(lines), lines
