"""slate-lint (slate_tpu.analysis): AST rules, baseline workflow, and the
compile-time collective race auditor.

Three layers:

* golden fixture snippets — one per rule ID, each making its rule fire
  exactly once (rule ID + line asserted), plus suppression/baseline
  round-trips;
* the clean-repo meta-test — ``lint(slate_tpu)`` must equal the committed
  baseline exactly (no new findings, no stale entries, every reason real);
* the collective auditor — synthetic HLO fixtures for the parser and every
  check, a real P=2 shard_map compile, and the corruption test (drop one
  participant's psum, the auditor must name it).
"""

import textwrap

import numpy as np
import pytest

from slate_tpu.analysis import (RULES, audit_hlo, extract_events,
                                participant_schedules, rule_table,
                                verify_events, verify_participant_schedules)
from slate_tpu.analysis import baseline as baseline_mod
from slate_tpu.analysis.lint import lint_package, lint_source

# ---------------------------------------------------------------------------
# Tier A: golden fixtures — (rule, relpath, snippet, expected line)

FIXTURES = {
    "SLT101": ("snippet.py", """\
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
        """, 5),
    "SLT102": ("snippet.py", """\
        import jax

        @jax.jit
        def f(x):
            return float(x)
        """, 5),
    "SLT103": ("snippet.py", """\
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.sum(x)
        """, 6),
    "SLT201": ("snippet.py", """\
        import jax

        def run_all(fns, x):
            out = []
            for fn in fns:
                out.append(jax.jit(fn)(x))
            return out
        """, 6),
    "SLT202": ("snippet.py", """\
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("opts",))
        def f(x, opts={}):
            return x
        """, 5),
    "SLT203": ("slate_tpu/serve/snippet.py", """\
        def key_for(routine, shape, opts):
            return (routine, shape, Options.make(opts))
        """, 2),
    "SLT301": ("snippet.py", """\
        import jax

        def setup():
            jax.config.update("jax_enable_x64", True)
        """, 4),
    "SLT302": ("snippet.py", """\
        import jax

        def f(x):
            jax.debug.print("x={}", x)
            return x
        """, 4),
    "SLT401": ("snippet.py", """\
        import jax

        def build(f):
            return jax.jit(f, static_argnums=(0,), donate_argnums=(0, 1))
        """, 4),
    "SLT501": ("snippet.py", """\
        def f():
            try:
                return work()
            except Exception:
                return None
        """, 4),
    "SLT601": ("slate_tpu/parallel/snippet.py", """\
        def gesv_snippet_distributed(a, b, grid):
            return a
        """, 1),
}


class TestRuleFixtures:
    @pytest.mark.parametrize("rule_id", sorted(RULES))
    def test_rule_fires_exactly_once(self, rule_id):
        assert rule_id in FIXTURES, f"no golden fixture for {rule_id}"
        relpath, snippet, line = FIXTURES[rule_id]
        findings = lint_source(textwrap.dedent(snippet), relpath=relpath)
        hits = [f for f in findings if f.rule == rule_id]
        assert len(hits) == 1, (
            f"{rule_id} fired {len(hits)}x on its fixture: {findings}")
        assert hits[0].line == line
        assert hits[0].severity == RULES[rule_id].severity

    def test_every_rule_has_fixture_and_registry_entry(self):
        assert set(FIXTURES) == set(RULES)
        assert len(RULES) >= 10          # the issue's "~10 rules" floor
        for rid, sev, title in rule_table():
            assert sev in ("error", "warning")
            assert title

    def test_shard_map_local_fn_counts_as_traced_core(self):
        src = textwrap.dedent("""\
            import jax

            def driver(a, mesh):
                def local_fn(al):
                    if al > 0:
                        return al
                    return -al
                return shard_map(local_fn, mesh=mesh)(a)
            """)
        hits = [f for f in lint_source(src) if f.rule == "SLT101"]
        assert len(hits) == 1 and hits[0].line == 5

    def test_static_safe_uses_do_not_fire(self):
        src = textwrap.dedent("""\
            import jax

            @jax.jit
            def f(x, q=None):
                if x.ndim == 2 and q is None:
                    return x
                return x.T
            """)
        assert [f for f in lint_source(src) if f.rule == "SLT101"] == []

    def test_static_argnames_params_do_not_fire(self):
        src = textwrap.dedent("""\
            import jax
            from functools import partial

            @partial(jax.jit, static_argnames=("nb",))
            def f(x, nb=32):
                if nb > 64:
                    return x
                return -x
            """)
        assert [f for f in lint_source(src) if f.rule == "SLT101"] == []

    def test_suppression_comment_silences_one_site(self):
        src = textwrap.dedent("""\
            def f():
                try:
                    return work()
                # slate-lint: disable=SLT501 -- fixture: intentional swallow
                except Exception:
                    return None
            """)
        assert [f for f in lint_source(src) if f.rule == "SLT501"] == []

    def test_broad_except_with_reraise_does_not_fire(self):
        src = textwrap.dedent("""\
            def f():
                try:
                    return work()
                except Exception:
                    cleanup()
                    raise
            """)
        assert [f for f in lint_source(src) if f.rule == "SLT501"] == []

    def test_directive_inside_string_literal_does_not_suppress(self):
        """The disable directive must be a real comment: a string that
        merely *mentions* it (debug payloads, rule docs) suppresses
        nothing — here the debug hook's own argument tries to silence the
        rule that flags it."""
        src = textwrap.dedent("""\
            import jax
            def f(x):
                jax.debug.print("# slate-lint: disable=SLT302 -- nope")
                return x
            """)
        assert [f for f in lint_source(src) if f.rule == "SLT302"]


# ---------------------------------------------------------------------------
# baseline workflow


class TestBaseline:
    def test_round_trip_absorbs_and_detects_new(self):
        src = textwrap.dedent("""\
            def f():
                try:
                    return work()
                except Exception:
                    return None
            """)
        findings = lint_source(src)
        doc = baseline_mod.build(findings)
        for e in doc["entries"]:
            e["reason"] = "fixture: accepted for the round-trip test"
        new, accepted, stale = baseline_mod.apply(findings, doc)
        assert new == [] and len(accepted) == len(findings) and stale == []
        # a second identical violation is NOT absorbed (count semantics)
        doubled = findings + findings
        new2, accepted2, _ = baseline_mod.apply(doubled, doc)
        assert len(new2) == len(findings)

    def test_validate_rejects_todo_reasons(self):
        doc = baseline_mod.build(
            lint_source(textwrap.dedent(FIXTURES["SLT501"][1])))
        problems = baseline_mod.validate(doc)
        assert problems and any("reason" in p for p in problems)

    def test_repo_lints_clean_against_committed_baseline(self):
        """The clean-repo meta-test: lint(slate_tpu) == baseline, exactly —
        no new findings, no stale entries, every entry's reason real."""
        doc = baseline_mod.load()
        assert baseline_mod.validate(doc) == []
        findings = lint_package()
        new, accepted, stale = baseline_mod.apply(findings, doc)
        assert new == [], "\n".join(f.render() for f in new)
        assert stale == [], f"stale baseline entries: {stale}"


# ---------------------------------------------------------------------------
# Tier B: collective race auditor — synthetic HLO fixtures

_HLO_CLEAN = """\
HloModule synthetic, is_scheduled=true

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main_spmd (p0: f32[4,4]) -> f32[4,4] {
  %p0 = f32[4,4]{1,0} parameter(0)
  %all-gather.1 = f32[8,4]{1,0} all-gather(f32[4,4]{1,0} %p0), channel_id=1, replica_groups={{0,1}}, dimensions={0}, use_global_device_ids=true
  %slice.1 = f32[4,4]{1,0} slice(f32[8,4]{1,0} %all-gather.1), slice={[0:4], [0:4]}
  ROOT %all-reduce.1 = f32[4,4]{1,0} all-reduce(f32[4,4]{1,0} %slice.1), channel_id=2, replica_groups={{0,1}}, use_global_device_ids=true, to_apply=%sum
}
"""

_HLO_COND = """\
HloModule synthetic_cond, is_scheduled=true

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

%branch_a (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %all-reduce.9 = f32[4]{0} all-reduce(f32[4]{0} %p), channel_id=7, replica_groups={{0,1}}, use_global_device_ids=true, to_apply=%sum
}

%branch_b (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %m = f32[4]{0} multiply(f32[4]{0} %p, f32[4]{0} %p)
}

ENTRY %main_spmd (p0: f32[4], i0: s32[]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %i0 = s32[] parameter(1)
  ROOT %conditional.1 = f32[4]{0} conditional(s32[] %i0, f32[4]{0} %p0, f32[4]{0} %p0), branch_computations={%branch_a, %branch_b}
}
"""

_HLO_CHAN_REUSE = """\
HloModule synthetic_chan, is_scheduled=true

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main_spmd (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %p0), channel_id=3, replica_groups={{0,1}}, use_global_device_ids=true, to_apply=%sum
  ROOT %all-reduce.2 = f32[4]{0} all-reduce(f32[4]{0} %all-reduce.1), channel_id=3, replica_groups={{0,1}}, use_global_device_ids=true, to_apply=%sum
}
"""

# predicate derived from a full-mesh all-reduce: every participant computes
# the same branch index, so the branch collective cannot deadlock — the
# auditor must prove this uniform and stay quiet (the CholQR fallback shape)
_HLO_COND_UNIFORM = """\
HloModule synthetic_cond_uniform, is_scheduled=true, num_partitions=2

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

%branch_a (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %all-gather.9 = f32[4]{0} all-gather(f32[4]{0} %p), channel_id=8, replica_groups={{0,1}}, dimensions={0}, use_global_device_ids=true
}

%branch_b (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %m = f32[4]{0} multiply(f32[4]{0} %p, f32[4]{0} %p)
}

ENTRY %main_spmd (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %all-reduce.5 = f32[4]{0} all-reduce(f32[4]{0} %p0), channel_id=1, replica_groups={{0,1}}, use_global_device_ids=true, to_apply=%sum
  %slice.5 = f32[1]{0} slice(f32[4]{0} %all-reduce.5), slice={[0:1]}
  %reshape.5 = f32[] reshape(f32[1]{0} %slice.5)
  %zero.5 = f32[] constant(0)
  %cmp.5 = pred[] compare(f32[] %reshape.5, f32[] %zero.5), direction=GT
  %idx.5 = s32[] convert(pred[] %cmp.5)
  ROOT %conditional.1 = f32[4]{0} conditional(s32[] %idx.5, f32[4]{0} %p0, f32[4]{0} %p0), branch_computations={%branch_a, %branch_b}
}
"""

_HLO_WHILE = """\
HloModule synthetic_while, is_scheduled=true

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[4]{0}) %t), index=0
  %x = f32[4]{0} get-tuple-element((s32[], f32[4]{0}) %t), index=1
  %collective-permute.1 = f32[4]{0} collective-permute(f32[4]{0} %x), channel_id=4, source_target_pairs={{0,1},{1,0}}
  ROOT %tup = (s32[], f32[4]{0}) tuple(s32[] %i, f32[4]{0} %collective-permute.1)
}

%cond (t: (s32[], f32[4])) -> pred[] {
  %t = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[4]{0}) %t), index=0
  ROOT %lt = pred[] compare(s32[] %i, s32[] %i), direction=LT
}

ENTRY %main_spmd (p0: f32[4]) -> (s32[], f32[4]) {
  %p0 = f32[4]{0} parameter(0)
  %c0 = s32[] constant(0)
  %tup0 = (s32[], f32[4]{0}) tuple(s32[] %c0, f32[4]{0} %p0)
  ROOT %while.1 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %tup0), condition=%cond, body=%body
}
"""


# a while whose condition reads partition-id: device trip counts diverge,
# so the body's all-reduce runs a different number of rendezvous per device
_HLO_WHILE_DIVERGENT = """\
HloModule synthetic_while_divergent, is_scheduled=true, num_partitions=2

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[4]{0}) %t), index=0
  %x = f32[4]{0} get-tuple-element((s32[], f32[4]{0}) %t), index=1
  %all-reduce.4 = f32[4]{0} all-reduce(f32[4]{0} %x), channel_id=4, replica_groups={{0,1}}, use_global_device_ids=true, to_apply=%sum
  ROOT %tup = (s32[], f32[4]{0}) tuple(s32[] %i, f32[4]{0} %all-reduce.4)
}

%cond (t: (s32[], f32[4])) -> pred[] {
  %t = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[4]{0}) %t), index=0
  %pid = u32[] partition-id()
  %pid_s = s32[] convert(u32[] %pid)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %pid_s), direction=LT
}

ENTRY %main_spmd (p0: f32[4]) -> (s32[], f32[4]) {
  %p0 = f32[4]{0} parameter(0)
  %c0 = s32[] constant(0)
  %tup0 = (s32[], f32[4]{0}) tuple(s32[] %c0, f32[4]{0} %p0)
  ROOT %while.1 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %tup0), condition=%cond, body=%body
}
"""


# carry laundering: no seed ever appears in the condition — the *body* folds
# partition-id into the counter carry, and the condition compares that
# counter against a constant.  Trip counts still diverge (device 0 adds 0
# per iteration and loops forever), so the body's rendezvous deadlocks.
_HLO_WHILE_CARRY_TAINT = """\
HloModule synthetic_while_carry_taint, is_scheduled=true, num_partitions=2

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[4]{0}) %t), index=0
  %x = f32[4]{0} get-tuple-element((s32[], f32[4]{0}) %t), index=1
  %pid = u32[] partition-id()
  %pid_s = s32[] convert(u32[] %pid)
  %inext = s32[] add(s32[] %i, s32[] %pid_s)
  %all-reduce.4 = f32[4]{0} all-reduce(f32[4]{0} %x), channel_id=4, replica_groups={{0,1}}, use_global_device_ids=true, to_apply=%sum
  ROOT %tup = (s32[], f32[4]{0}) tuple(s32[] %inext, f32[4]{0} %all-reduce.4)
}

%cond (t: (s32[], f32[4])) -> pred[] {
  %t = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[4]{0}) %t), index=0
  %c10 = s32[] constant(10)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %c10), direction=LT
}

ENTRY %main_spmd (p0: f32[4]) -> (s32[], f32[4]) {
  %p0 = f32[4]{0} parameter(0)
  %c0 = s32[] constant(0)
  %tup0 = (s32[], f32[4]{0}) tuple(s32[] %c0, f32[4]{0} %p0)
  ROOT %while.1 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %tup0), condition=%cond, body=%body
}
"""


# precision counterpart: partition-id taints only the *data* carry element
# (shard indexing, ubiquitous in the registry's loops) while the condition
# reads the counter, updated by a constant add — trip counts are uniform
# and the auditor must stay quiet
_HLO_WHILE_DATA_TAINT = """\
HloModule synthetic_while_data_taint, is_scheduled=true, num_partitions=2

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[4]{0}) %t), index=0
  %x = f32[4]{0} get-tuple-element((s32[], f32[4]{0}) %t), index=1
  %c1 = s32[] constant(1)
  %inext = s32[] add(s32[] %i, s32[] %c1)
  %pid = u32[] partition-id()
  %pid_f = f32[] convert(u32[] %pid)
  %pid_b = f32[4]{0} broadcast(f32[] %pid_f), dimensions={}
  %xs = f32[4]{0} add(f32[4]{0} %x, f32[4]{0} %pid_b)
  %all-reduce.4 = f32[4]{0} all-reduce(f32[4]{0} %xs), channel_id=4, replica_groups={{0,1}}, use_global_device_ids=true, to_apply=%sum
  ROOT %tup = (s32[], f32[4]{0}) tuple(s32[] %inext, f32[4]{0} %all-reduce.4)
}

%cond (t: (s32[], f32[4])) -> pred[] {
  %t = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[4]{0}) %t), index=0
  %c10 = s32[] constant(10)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %c10), direction=LT
}

ENTRY %main_spmd (p0: f32[4]) -> (s32[], f32[4]) {
  %p0 = f32[4]{0} parameter(0)
  %c0 = s32[] constant(0)
  %tup0 = (s32[], f32[4]{0}) tuple(s32[] %c0, f32[4]{0} %p0)
  ROOT %while.1 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %tup0), condition=%cond, body=%body
}
"""


# one permute, direction 0->1; the corrupted peer compiles the reverse
_HLO_PERMUTE = """\
HloModule synthetic_permute, is_scheduled=true, num_partitions=2

ENTRY %main_spmd (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %collective-permute.1 = f32[4]{0} collective-permute(f32[4]{0} %p0), channel_id=5, source_target_pairs={{0,1}}
}
"""


class TestCollectiveAuditSynthetic:
    def test_extract_events_order_and_attrs(self):
        events = extract_events(_HLO_CLEAN)
        assert [e.op for e in events] == ["all-gather", "all-reduce"]
        assert [e.channel_id for e in events] == [1, 2]
        assert events[0].groups == ((0, 1),)
        assert events[0].while_depth == 0 and events[0].branch_path == ()

    def test_clean_schedule_verifies(self):
        out = audit_hlo(_HLO_CLEAN, nproc=2)
        assert out["collective_sites"] == 2
        assert out["findings"] == []

    def test_conditional_collective_is_flagged(self):
        out = audit_hlo(_HLO_COND, nproc=2)
        assert any("conditional branch" in f for f in out["findings"])
        # the event knows which branch it sits under
        ev = [e for e in extract_events(_HLO_COND) if e.op == "all-reduce"]
        assert len(ev) == 1 and ev[0].branch_path[0][1] == 0

    def test_uniform_predicate_cond_is_proven_safe(self):
        """Predicate chains back to a full-mesh all-reduce: the conditional
        cannot diverge, the branch collective is safe, no finding."""
        events = extract_events(_HLO_COND_UNIFORM)
        ev = [e for e in events if e.op == "all-gather"]
        assert len(ev) == 1 and ev[0].branch_path and ev[0].cond_uniform
        assert audit_hlo(_HLO_COND_UNIFORM, nproc=2)["findings"] == []
        assert audit_hlo(_HLO_COND_UNIFORM, nproc=2)[
            "uniform_cond_sites"] == 1

    def test_channel_reuse_is_flagged(self):
        out = audit_hlo(_HLO_CHAN_REUSE, nproc=2)
        assert any("channel 3 reused" in f for f in out["findings"])

    def test_while_body_collective_found_with_depth(self):
        events = extract_events(_HLO_WHILE)
        perm = [e for e in events if e.op == "collective-permute"]
        assert len(perm) == 1
        assert perm[0].while_depth == 1
        assert perm[0].groups == ((0, 1),)
        assert audit_hlo(_HLO_WHILE, nproc=2)["findings"] == []

    def test_divergent_while_condition_is_flagged(self):
        """A while condition reading partition-id gives the mesh divergent
        trip counts: the body's rendezvous count differs per device.  The
        counter-driven _HLO_WHILE above must stay clean (loop carries are
        not divergence seeds)."""
        out = audit_hlo(_HLO_WHILE_DIVERGENT, nproc=2)
        assert any("while loop whose condition" in f for f in out["findings"])
        ev = [e for e in extract_events(_HLO_WHILE_DIVERGENT)
              if e.op == "all-reduce"]
        assert len(ev) == 1 and ev[0].while_divergent

    def test_carry_laundered_divergent_while_is_flagged(self):
        """No seed in the condition — the body folds partition-id into the
        counter carry and the condition compares it to a constant.  Trip
        counts still diverge; the carry-taint dataflow must catch it."""
        out = audit_hlo(_HLO_WHILE_CARRY_TAINT, nproc=2)
        assert any("while loop whose condition" in f for f in out["findings"])

    def test_seed_tainted_data_carry_stays_clean(self):
        """partition-id in the *data* carry element only (shard indexing,
        everywhere in the registry's loops) with a counter-read condition:
        trip counts are uniform, no finding — the precision half of the
        carry-taint analysis."""
        assert audit_hlo(_HLO_WHILE_DATA_TAINT, nproc=2)["findings"] == []

    def test_permute_direction_mismatch_is_reported(self):
        """Two independently compiled peers disagree on a permute's
        direction: groups flatten to the same device set, so identity must
        include source_target_pairs for the comparator to see it."""
        fwd = extract_events(_HLO_PERMUTE, nproc=2)
        rev = extract_events(
            _HLO_PERMUTE.replace("{{0,1}}", "{{1,0}}"), nproc=2)
        assert fwd[0].pairs == ((0, 1),) and rev[0].pairs == ((1, 0),)
        findings = verify_participant_schedules({0: fwd, 1: rev}, nproc=2)
        assert any("disagree" in f for f in findings)
        # agreeing directions stay clean
        assert verify_participant_schedules(
            {0: fwd, 1: list(fwd)}, nproc=2) == []

    def test_audit_nproc_overrides_module_inference(self):
        """Without a num_partitions header, mesh size inferred from the
        largest participant under-counts when every collective is a
        subgroup one — the caller's nproc must win, or a subgroup
        rendezvous masquerades as full-mesh and falsely proves a divergent
        predicate uniform."""
        hlo = _HLO_COND_UNIFORM.replace(", num_partitions=2", "")
        assert audit_hlo(hlo, nproc=2)["findings"] == []   # truly full-mesh
        out = audit_hlo(hlo, nproc=4)
        assert any("not provably uniform" in f for f in out["findings"])

    def test_out_of_mesh_participant_is_flagged(self):
        out = audit_hlo(_HLO_CLEAN, nproc=1)
        assert any("outside the P=1 mesh" in f for f in out["findings"])

    def test_corrupted_schedule_missing_psum_is_reported(self):
        """THE corruption test: drop one participant's psum from the
        projected schedules and the cross-participant check must name the
        missing rendezvous and the device that blocks."""
        events = extract_events(_HLO_CLEAN)
        sched = participant_schedules(events, nproc=2)
        assert len(sched[0]) == len(sched[1]) == 2
        dropped = [e for e in sched[1] if e.op != "all-reduce"]
        findings = verify_participant_schedules({0: sched[0], 1: dropped},
                                                nproc=2)
        assert findings, "auditor missed the dropped psum"
        assert any("all-reduce" in f and "missing" in f for f in findings)

    def test_reordered_schedule_is_reported(self):
        events = extract_events(_HLO_CLEAN)
        sched = participant_schedules(events, nproc=2)
        findings = verify_participant_schedules(
            {0: sched[0], 1: list(reversed(sched[1]))}, nproc=2)
        assert any("disagree" in f for f in findings)


# ---------------------------------------------------------------------------
# Tier B against real compiled programs (virtual CPU mesh)


class TestCollectiveAuditCompiled:
    def test_p2_shard_map_program_clean_then_corrupted(self):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from slate_tpu.parallel import ProcessGrid
        from slate_tpu.parallel.mesh import COL_AXIS, ROW_AXIS
        from jax.sharding import PartitionSpec as P

        g = ProcessGrid(devices=jax.devices()[:2])
        ax = ROW_AXIS if g.p > 1 else COL_AXIS

        def local_fn(al):
            s = lax.psum(al, ax)
            gathered = lax.all_gather(al, ax)
            return s + gathered.sum(axis=0)

        fn = jax.shard_map(local_fn, mesh=g.mesh, in_specs=P(ax, None),
                       out_specs=P(ax, None))
        compiled = jax.jit(fn).lower(
            jnp.ones((8, 4), jnp.float32)).compile()
        events = extract_events(compiled.as_text())
        ops = {e.op for e in events}
        assert "all-reduce" in ops and "all-gather" in ops
        assert verify_events(events, 2) == []
        sched = participant_schedules(events, 2)
        assert verify_participant_schedules(sched, 2) == []
        # corrupt: participant 1 skips its psum
        sched[1] = [e for e in sched[1] if e.op != "all-reduce"]
        assert verify_participant_schedules(sched, 2)

    def test_p2_audit_one_registry_routine(self):
        from slate_tpu.analysis import audit_routines

        rows = audit_routines(pset=(2,), names=("gemm_allgather",))
        assert len(rows) == 1
        row = rows[0]
        assert not row.get("error") and not row.get("skipped")
        assert row["collective_sites"] >= 1
        assert row["findings"] == []

    @pytest.mark.slow
    def test_full_registry_schedules_consistent_p2(self):
        from slate_tpu.analysis import audit_routines
        from slate_tpu.analysis.collective_audit import summarize

        rows = audit_routines(pset=(2,))
        audited, nfind, lines = summarize(rows)
        assert audited >= 25
        assert nfind == 0, "\n".join(lines)
