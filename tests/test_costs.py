"""Cost-provider layer: analytic + HLO-measured per-region attributes.

Fixture HLO text lives in tests/data/hlo/ (regenerate the two compiled
modules with make_hlo_fixtures.py; regions_handwritten.hlo.txt is
hand-written to pin the computation-name prefix matching exactly).  The
fixtures are parsed as plain text — no jax needed anywhere in this file.
"""
import pathlib

import numpy as np
import pytest

from repro.core import (AnalysisSession, PolicyEngine, RegionTree,
                        ReshardPolicy, ROLE_MEMORY, ROLE_NETWORK, ROLE_WORK)
from repro.launch.hlo_analysis import Analyzer
from repro.launch.steps import hlo_cost_provider
from repro.perfdbg import (AnalyticCosts, HloCosts, Instrumenter,
                           RegionRecorder, boundedness_ratios)
from repro.perfdbg.attributes import RIDGE_INTENSITY
from repro.perfdbg.schema import (AttributeField, AttributeSchema,
                                  PAPER_SCHEMA, TPU_SCHEMA, SUM)

HLO_DIR = pathlib.Path(__file__).parent / "data" / "hlo"


def fixture(name: str) -> str:
    return (HLO_DIR / name).read_text()


def small_tree(names=("data", "step", "checkpoint")):
    t = RegionTree()
    for nm in names:
        t.add(nm)
    return t


# ---------------------------------------------------------------------------
# Schema: provider keys + roles are collection-side metadata
# ---------------------------------------------------------------------------

class TestSchemaProviderMetadata:
    def test_provider_key_and_role_do_not_change_layout_identity(self):
        """Wire compat: provider keys/roles never change the layout
        fingerprint (how a cell is filled does not change what its bytes
        mean).  The role does ride the spec — receivers interpret cores
        through it — but the provider key is collection-side only."""
        bare = AttributeSchema("meta_t", (AttributeField("a", SUM),))
        tagged = AttributeSchema("meta_t", (
            AttributeField("a", SUM, provider_key="hlo_flops",
                           role=ROLE_WORK),))
        assert bare.fingerprint() == tagged.fingerprint()
        assert bare.dtype() == tagged.dtype()
        assert tagged.to_spec() == [["a", SUM, None, None, ROLE_WORK]]
        assert "hlo_flops" not in repr(tagged.to_spec())

    def test_values_from_provider_maps_declared_keys_only(self):
        costs = {"hlo_flops": 5.0, "hbm_bytes": 7.0, "collective_bytes": 3.0,
                 "host_io_bytes": 2.0, "hbm_boundedness": 0.5,
                 "vmem_pressure": 0.25}
        tpu = TPU_SCHEMA.values_from_provider(costs)
        assert tpu == {"hlo_flops": 5.0, "collective_bytes": 3.0,
                       "host_io_bytes": 2.0, "hbm_boundedness": 0.5,
                       "vmem_pressure": 0.25}       # hbm_bytes: no field
        paper = PAPER_SCHEMA.values_from_provider(costs)
        assert paper == {"instr_attr": 5.0, "network_io": 3.0,
                         "disk_io": 2.0, "l2_miss_rate": 0.5,
                         "l1_miss_rate": 0.25}
        # partial cost dicts fill only what they cover
        assert TPU_SCHEMA.values_from_provider({"hlo_flops": 1.0}) == \
            {"hlo_flops": 1.0}

    def test_builtin_roles_declared(self):
        assert TPU_SCHEMA.roles_by_export() == {
            "vmem_pressure": ROLE_MEMORY, "hbm_boundedness": ROLE_MEMORY,
            "host_io_bytes": "io", "collective_bytes": ROLE_NETWORK,
            "hlo_flops": ROLE_WORK}
        assert PAPER_SCHEMA.roles_by_export()["instructions"] == ROLE_WORK


# ---------------------------------------------------------------------------
# AnalyticCosts: the estimates extracted from launch/train.py
# ---------------------------------------------------------------------------

class TestAnalyticCosts:
    def test_for_train_step_formulas(self):
        p = AnalyticCosts.for_train_step(
            active_params=1e6, total_params=2e6, d_model=128, n_layers=4,
            tokens_per_step=256, checkpoint_io_bytes=1.0)
        step = p.region_costs("step")
        assert step["hlo_flops"] == 6.0 * 1e6 * 256
        assert step["hbm_bytes"] == 2.0 * 2e6 * 2 + 8.0 * 256 * 128 * 4
        assert step["collective_bytes"] == 0.0
        expect = boundedness_ratios(step["hlo_flops"], step["hbm_bytes"])
        assert step["hbm_boundedness"] == expect["hbm_boundedness"]
        assert p.region_costs("data") == {"host_io_bytes": 8.0 * 256}
        assert p.region_costs("checkpoint") == {"host_io_bytes": 1.0}
        assert p.region_costs("nonexistent") == {}

    def test_boundedness_ratios(self):
        flat = boundedness_ratios(1.0, 1.0)       # intensity 1 << ridge
        assert flat["hbm_boundedness"] == pytest.approx(
            1.0 - 1.0 / RIDGE_INTENSITY)
        assert flat["vmem_pressure"] == flat["hbm_boundedness"] / 2
        # far above the ridge: compute-bound, clipped to 0
        hot = boundedness_ratios(1e12, 1.0)
        assert hot["hbm_boundedness"] == 0.0


# ---------------------------------------------------------------------------
# HLO fixtures: measured per-region numbers
# ---------------------------------------------------------------------------

class TestStepSpmdFixture:
    """Compiled 2-device module: scan of (4,32)@(32,32) matmuls x 4 trips +
    a global loss all-reduce (see make_hlo_fixtures.py)."""

    def test_trip_aware_flops_and_collectives(self):
        a = Analyzer(fixture("step_spmd.hlo.txt"))
        st = a.stats()
        # 4 trips x 2*4*32*32 dot flops dominate; trip-unaware would be 1/4
        assert st.flops >= 4 * (2 * 4 * 32 * 32)
        assert st.collective_counts["all-reduce"] == 1
        assert st.total_collective_bytes == 4.0        # f32[] loss
        d = st.as_dict()
        assert d["total_collective_bytes"] == \
            sum(d["collective_bytes"].values()) == 4.0

    def test_stats_by_computation_entry_matches_stats(self):
        a = Analyzer(fixture("step_spmd.hlo.txt"))
        by_comp = a.stats_by_computation()
        assert set(by_comp) == set(a.comps)
        assert by_comp[a.entry] is a.stats()           # same memoized object
        # the while body is a computation of its own, counted once there
        bodies = [n for n in by_comp if "region_0" in n]
        assert bodies and by_comp[bodies[0]].flops >= 2 * 4 * 32 * 32

    def test_hlo_costs_anchor_carries_module(self):
        """No computation is named after a region: everything rides the
        residual on the anchor, and coverage says so explicitly."""
        base = AnalyticCosts({"data": {"host_io_bytes": 99.0}})
        prov = hlo_cost_provider(fixture("step_spmd.hlo.txt"),
                                 ("data", "step", "checkpoint"),
                                 anchor="step", base=base)
        st = Analyzer(fixture("step_spmd.hlo.txt")).stats()
        step = prov.region_costs("step")
        assert step["hlo_flops"] == st.flops
        assert step["hbm_bytes"] == st.bytes
        assert step["collective_bytes"] == 4.0
        assert 0.0 <= step["hbm_boundedness"] <= 1.0
        cov = prov.coverage()["step"]
        assert cov.coverage == 0.0 and cov.matched == ()
        assert cov.residual_flops == st.flops
        assert prov.residual("step") == st.flops
        # base fallthrough for regions the module can't see
        assert prov.region_costs("data") == {"host_io_bytes": 99.0}
        assert prov.region_costs("checkpoint") == {}
        assert "step" in prov.render_coverage()


class TestWhileSlicedFixture:
    """Compiled scan over xs: while body dynamic-slices the stacked operand
    (trip count 8, slice (1,16,16) of an (8,16,16) buffer)."""

    def test_trip_count_multiplies_body(self):
        a = Analyzer(fixture("while_sliced.hlo.txt"))
        # 8 trips x 2*16*16*16 dot flops
        assert a.stats().flops >= 8 * (2 * 16 * 16 * 16)
        assert a.stats().flops < 3 * 8 * (2 * 16 * 16 * 16)

    def test_sliced_param_bytes(self):
        """The fusion reads the (1,16,16) slice per iteration, not the full
        (8,16,16) buffer — 1024 bytes, not 8192."""
        a = Analyzer(fixture("while_sliced.hlo.txt"))
        fused = next(c for n, c in a.comps.items() if "fused" in n
                     and any("dynamic-slice" in o.line for o in c.ops))
        assert a._sliced_params(fused) == {0: 4.0 * 1 * 16 * 16}

    def test_provider_numbers_from_sliced_module(self):
        prov = HloCosts(("step",)).add_module(
            Analyzer(fixture("while_sliced.hlo.txt")).stats_by_computation(),
            entry=Analyzer(fixture("while_sliced.hlo.txt")).entry,
            anchor="step")
        costs = prov.region_costs("step")
        assert costs["hlo_flops"] == \
            Analyzer(fixture("while_sliced.hlo.txt")).stats().flops
        assert costs["collective_bytes"] == 0.0


class TestRegionPrefixMatching:
    """Hand-written module pinning the attribution arithmetic exactly.

    Standalone flops (elementwise at 1 flop/element; parameters cost
    none): attn.fwd = 1024 (dot); ffn_fwd = 2048 (dot); sum.helper = 1;
    main = 1024 + 64 (add) + 2048 = 3136, plus a 256-byte all-reduce."""

    def make(self, regions=("outer", "attn", "ffn")):
        a = Analyzer(fixture("regions_handwritten.hlo.txt"))
        return HloCosts(regions).add_module(a.stats_by_computation(),
                                            entry=a.entry, anchor="outer")

    def test_exact_attribution(self):
        prov = self.make()
        assert prov.region_costs("attn")["hlo_flops"] == 1024.0
        assert prov.region_costs("ffn")["hlo_flops"] == 2048.0
        outer = prov.region_costs("outer")
        assert outer["hlo_flops"] == 3136.0 - 1024.0 - 2048.0   # residual
        assert outer["collective_bytes"] == 256.0    # stays on the anchor

    def test_coverage_accounting(self):
        cov = self.make().coverage()["outer"]
        assert cov.total_flops == 3136.0
        assert cov.attributed_flops == 3072.0
        assert cov.residual_flops == 64.0
        assert cov.coverage == pytest.approx(3072.0 / 3136.0)
        assert cov.matched == (("attn.fwd", "attn"), ("ffn_fwd", "ffn"))
        assert cov.unmatched == 1                    # sum.helper
        assert "outer" in cov.render()

    def test_unknown_names_raise(self):
        a = Analyzer(fixture("regions_handwritten.hlo.txt"))
        with pytest.raises(KeyError):
            HloCosts(("outer",)).add_module(a.stats_by_computation(),
                                            entry=a.entry, anchor="step")
        with pytest.raises(KeyError):
            HloCosts(("outer",)).add_module({}, entry="main", anchor="outer")

    def test_anchor_only_attribution_keeps_totals(self):
        """Without named regions the anchor carries the whole module —
        nothing is lost, it is just unattributed."""
        solo = self.make(regions=("outer",))
        assert solo.region_costs("outer")["hlo_flops"] == 3136.0
        assert solo.coverage()["outer"].coverage == 0.0


# ---------------------------------------------------------------------------
# Recorder integration: provider-fed == kwargs-fed, byte for byte
# ---------------------------------------------------------------------------

def drive(rec, values_by_region, steps=3):
    """Simulate `steps` executions of each region with fixed timings."""
    ins = Instrumenter(rec, 0)
    rids = {rec.tree.name(r): r for r in rec.tree.ids()}
    for _ in range(steps):
        for nm, vals in values_by_region.items():
            rec.add(0, rids[nm], cpu_time=0.5, wall_time=1.0, cycles=1e9,
                    instructions=1e6, **vals)
        rec.add_program_wall(0, 3.0)
    return rec


class TestRecorderProvider:
    COSTS = {"data": {"host_io_bytes": 2048.0},
             "step": {"hlo_flops": 5e9, "hbm_bytes": 4e9,
                      "collective_bytes": 1e6,
                      **boundedness_ratios(5e9, 4e9)},
             "checkpoint": {"host_io_bytes": 1.0}}

    def _kwargs_equiv(self, schema):
        return {nm: schema.values_from_provider(c)
                for nm, c in self.COSTS.items()}

    @pytest.mark.parametrize("schema", ["tpu", "paper"])
    def test_provider_fed_equals_kwargs_fed_bytes(self, schema):
        t = small_tree()
        fed = drive(RegionRecorder(t, 1, schema=schema,
                                   cost_provider=AnalyticCosts(self.COSTS)),
                    {nm: {} for nm in self.COSTS})
        sc = fed.schema
        explicit = drive(RegionRecorder(t, 1, schema=schema),
                         self._kwargs_equiv(sc))
        assert fed.snapshot("w").to_bytes() == \
            explicit.snapshot("w").to_bytes()

    def test_provider_swap_byte_identical_reports(self):
        """Acceptance: two different provider implementations fed identical
        cost values produce byte-identical session reports."""
        t = small_tree()
        analytic = AnalyticCosts(self.COSTS)
        hlo_like = HloCosts(tuple(self.COSTS), base=AnalyticCosts(self.COSTS))
        reports = []
        for prov in (analytic, hlo_like):
            rec = drive(RegionRecorder(t, 4, schema="tpu",
                                       cost_provider=prov),
                        {nm: {} for nm in self.COSTS})
            s = AnalysisSession(t)
            s.ingest_snapshot(rec.reset_window("w0"))
            reports.append(s.report().render(t))
        assert reports[0] == reports[1]

    def test_explicit_kwarg_beats_provider(self):
        t = small_tree(("step",))
        rec = RegionRecorder(t, 1, schema="tpu",
                             cost_provider=AnalyticCosts(
                                 {"step": {"hlo_flops": 111.0}}))
        rid = t.ids()[0]
        rec.add(0, rid, wall_time=1.0, hlo_flops=999.0)
        assert rec.attributes()["hlo_flops"][0, 0] == 999.0
        rec.add(0, rid, wall_time=1.0)              # provider fills this one
        assert rec.attributes()["hlo_flops"][0, 0] == 999.0 + 111.0

    def test_source_mirror_when_provider_lacks_key(self):
        """provider > source precedence, but an uncovered field still falls
        back to its locate-field mirror."""
        t = small_tree(("step",))
        rec = RegionRecorder(t, 1, schema="tpu",
                             cost_provider=AnalyticCosts({"step": {}}))
        rec.add(0, t.ids()[0], wall_time=1.0, instructions=7e6)
        assert rec.attributes()["hlo_flops"][0, 0] == 7e6

    def test_attach_provider_resets_memo(self):
        t = small_tree(("step",))
        rec = RegionRecorder(t, 1, schema="tpu",
                             cost_provider=AnalyticCosts(
                                 {"step": {"hlo_flops": 1.0}}))
        rid = t.ids()[0]
        rec.add(0, rid, wall_time=1.0)
        rec.attach_provider(AnalyticCosts({"step": {"hlo_flops": 10.0}}))
        rec.add(0, rid, wall_time=1.0)
        assert rec.attributes()["hlo_flops"][0, 0] == 11.0
        assert rec.cost_provider is not None

    def test_snapshot_roundtrip_preserves_provider_fed_cells(self):
        t = small_tree()
        rec = drive(RegionRecorder(t, 2, schema="tpu",
                                   cost_provider=AnalyticCosts(self.COSTS)),
                    {nm: {} for nm in self.COSTS})
        snap = rec.snapshot("w")
        from repro.perfdbg import WindowSnapshot
        back = WindowSnapshot.from_bytes(snap.to_bytes())
        assert back.to_bytes() == snap.to_bytes()
        assert back.attribute_roles() == snap.attribute_roles()


# ---------------------------------------------------------------------------
# Roles end-to-end: schema -> snapshot -> entry -> policy
# ---------------------------------------------------------------------------

class TestRolesEndToEnd:
    def fill(self, rec, m, work_skew=None):
        work_skew = work_skew or {}
        for r in range(m):
            f = work_skew.get(r, 1.0)
            for rid in rec.tree.ids():
                rec.add(r, rid, cpu_time=f, wall_time=f, cycles=f * 2e9,
                        instructions=1e9 * f, host_io_bytes=64.0 * f,
                        collective_bytes=8.0)
            rec.add_program_wall(r, 3.0 * f)

    def test_reshard_fires_on_tpu_work_role(self):
        """Under the tpu schema the work attribute is named hlo_flops; the
        role declaration (not the name) is what the policy matches — and a
        co-varying io attribute tying in the minimal cores must not hide
        the work signal."""
        t = small_tree(("r1", "r2", "r3"))
        rec = RegionRecorder(t, 6, schema="tpu")
        session = AnalysisSession(t)
        engine = PolicyEngine([ReshardPolicy()], k=2, cooldown=0)
        fired = []
        for _ in range(2):
            self.fill(rec, 6, work_skew={5: 4.0})
            entry = session.ingest_recorder(rec)
            assert entry.role_of("hlo_flops") == ROLE_WORK
            alts = entry.core_alternatives("external")
            assert any("hlo_flops" in c for c in alts)
            fired += engine.observe(entry, session)
        assert len(fired) == 1
        assert fired[0].kind == "reshard" and fired[0].target == "hlo_flops"
        assert fired[0].params["role"] == ROLE_WORK

    def test_reshard_quiet_without_work_signal(self):
        t = small_tree(("r1", "r2", "r3"))
        rec = RegionRecorder(t, 6, schema="tpu")
        session = AnalysisSession(t)
        engine = PolicyEngine([ReshardPolicy()], k=1)
        for r in range(6):                      # speed imbalance: same work
            f = 4.0 if r == 5 else 1.0
            for rid in t.ids():
                rec.add(r, rid, cpu_time=f, wall_time=f, cycles=f * 2e9,
                        instructions=1e9)
            rec.add_program_wall(r, 3.0 * f)
        entry = session.ingest_recorder(rec)
        assert engine.observe(entry, session) == []

    def test_roles_recorded_on_root_cause_reports(self):
        t = small_tree(("r1", "r2", "r3"))
        rec = RegionRecorder(t, 6, schema="tpu")
        self.fill(rec, 6, work_skew={5: 4.0})
        session = AnalysisSession(t)
        entry = session.ingest_recorder(rec)
        rc = entry.report.external_root_causes
        assert rc is not None and dict(rc.roles)["hlo_flops"] == ROLE_WORK
        assert rc.role_of("no_such_attr") is None

    def test_roles_survive_wire_transport_of_unregistered_schema(self):
        """A pod's analysis host rebuilds unregistered schemas from the
        wire spec — the role declarations must ride along (else role-driven
        policies silently degrade on exactly the transport path), while
        provider_key stays collection-side and the fingerprint ignores
        both (pre-role 4-entry specs still parse)."""
        from repro.perfdbg import WindowSnapshot
        custom = AttributeSchema("custom_roles_t", (
            AttributeField("flops2", SUM, provider_key="hlo_flops",
                           role=ROLE_WORK),))
        t = small_tree(("r1",))
        rec = RegionRecorder(t, 1, schema=custom)
        rec.add(0, t.ids()[0], wall_time=1.0, flops2=5.0)
        back = WindowSnapshot.from_bytes(rec.snapshot("w").to_bytes())
        assert back.attribute_roles() == {"flops2": ROLE_WORK}
        assert back.schema.fields[0].provider_key is None   # not shipped
        assert back.schema.fingerprint() == custom.fingerprint()
        # pre-role spec (4 entries) parses with role=None
        old = AttributeSchema.from_spec("custom_roles_t",
                                        [["flops2", SUM, None, None]])
        assert old.fingerprint() == custom.fingerprint()
        assert old.roles_by_export() == {}

    def test_raw_ingest_without_roles_falls_back_to_paper_name(self):
        """Streams that never declared roles keep the paper's behavior:
        the policy matches the attribute literally named 'instructions'."""
        t = small_tree(("r1",))
        m = 6
        cpu = np.ones((m, 1))
        cpu[5] = 4.0
        instr = np.ones((m, 1)) * 1e9
        instr[5] *= 4.0
        from repro.core import Measurements
        meas = Measurements(cpu_time=cpu, wall_time=cpu,
                            program_wall=np.full(m, 3.0),
                            cycles=cpu * 2e9, instructions=instr)
        session = AnalysisSession(t)
        entry = session.ingest(meas, {"instructions": instr})
        assert entry.role_of("instructions") is None
        engine = PolicyEngine([ReshardPolicy()], k=1)
        fired = engine.observe(entry, session)
        assert [a.target for a in fired] == ["instructions"]
