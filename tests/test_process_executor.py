"""``AsyncAnalysisSession(executor="process")``: the prepare stage runs in
spawn-pool session replicas past the GIL, yet the rendered report must stay
byte-identical — and the PolicyLog identical — to the synchronous session
for any worker count and executor kind, with supervision tombstoning the
same windows under injected analyzer faults."""
import numpy as np
import pytest

from repro.core import (AnalysisSession, AsyncAnalysisSession, PolicyEngine,
                        RegionTree)
from repro.core.pipeline import EXECUTOR_KINDS, PROCESS, THREAD
from repro.core.policy import RebalancePolicy
from repro.perfdbg import RegionRecorder
from repro.perfdbg.chaos import ChaosInjector, ChaosSession, run_chaos


def small_tree(n=3):
    t = RegionTree()
    for i in range(1, n + 1):
        t.add(f"r{i}", rid=i)
    return t


def straggler_stream(tree, n_windows, n_ranks=6):
    """Rank 5 straggles from window 2 on — hot enough to fire policies."""
    rec = RegionRecorder(tree, n_ranks, max_windows=max(n_windows, 1))
    for w in range(n_windows):
        for r in range(n_ranks):
            f = 4.0 if (r == n_ranks - 1 and w >= 2) else 1.0
            for rid in tree.ids():
                rec.add(r, rid, cpu_time=f, wall_time=f, cycles=f * 2e9,
                        instructions=1e9)
            rec.add_program_wall(r, float(len(tree.ids())) * f)
        rec.reset_window(f"w{w}")
    return rec.windows()


def run_pipeline(tree, snaps, *, executor, workers, session=None,
                 supervised=False, with_policies=False):
    engine = PolicyEngine([RebalancePolicy()], k=2, cooldown=0) \
        if with_policies else None
    pipe = AsyncAnalysisSession(tree, workers=workers, executor=executor,
                                session=session, supervised=supervised,
                                escalate_after=10**9 if supervised else 3,
                                policy_engine=engine)
    for s in snaps:
        pipe.submit(s)
    report = pipe.close(timeout=120.0)
    log = [d.render() for d in engine.log.decisions] if engine else []
    failed = tuple(e.index for e in report.windows if e.failed)
    return report.render(tree), log, failed, pipe


class TestExecutorEquivalence:
    @pytest.mark.parametrize("workers", [1, 2, 4])
    def test_process_report_and_policy_log_identical_to_sync(self, workers):
        tree = small_tree()
        snaps = straggler_stream(tree, 8)
        sync = AnalysisSession(tree)
        sync_engine = PolicyEngine([RebalancePolicy()], k=2, cooldown=0)
        for s in snaps:
            entry = sync.ingest_snapshot(s)
            sync_engine.observe(entry, sync)
        sync_log = [d.render() for d in sync_engine.log.decisions]
        assert sync_log   # the straggler stream must actually fire decisions

        text, log, failed, pipe = run_pipeline(
            tree, snaps, executor=PROCESS, workers=workers,
            with_policies=True)
        assert text == sync.report().render(tree)
        assert log == sync_log
        assert failed == ()
        assert pipe.analyzed == 8 and pipe.failed == 0

    def test_supervised_faults_tombstone_same_windows_across_executors(self):
        """Forced analyzer faults at windows 2 and 5: the process executor
        fires them parent-side (``check_analyzer_fault``), so tombstones
        land in the identical timeline slots as the thread executor's."""
        tree = small_tree()
        snaps = straggler_stream(tree, 8)
        force = {"analyzer": [(2, 0), (5, 0)]}
        outcomes = {}
        for executor, workers in [(THREAD, 1), (THREAD, 3), (PROCESS, 2)]:
            session = ChaosSession(
                tree, ChaosInjector(0, rates={}, force=force))
            text, _, failed, pipe = run_pipeline(
                tree, snaps, executor=executor, workers=workers,
                session=session, supervised=True)
            assert failed == (2, 5)
            assert pipe.analyzed == 6 and pipe.failed == 2
            assert pipe.analyzed + pipe.failed + pipe.dropped \
                == pipe.submitted
            outcomes[(executor, workers)] = text
        assert len(set(outcomes.values())) == 1

    def test_executor_validation(self):
        with pytest.raises(ValueError, match="executor"):
            AsyncAnalysisSession(small_tree(), executor="greenlet")
        assert THREAD in EXECUTOR_KINDS and PROCESS in EXECUTOR_KINDS

    def test_process_executor_respects_custom_session_config(self):
        """The spawn replicas read their knobs off the wrapped session —
        a gated session must gate identically in both executors."""
        tree = small_tree()
        snaps = straggler_stream(tree, 5)
        texts = []
        for executor in (THREAD, PROCESS):
            session = AnalysisSession(tree, internal_gate_s=1e9,
                                      collapse="exact")
            pipe = AsyncAnalysisSession(tree, session=session,
                                        executor=executor, workers=2)
            for s in snaps:
                pipe.submit(s)
            report = pipe.close(timeout=120.0)
            assert report.cache_hit_counts().get("internal_gated", 0) > 0
            texts.append(report.render(tree))
        assert texts[0] == texts[1]


def test_run_chaos_process_executor_accounting():
    """The chaos soak's survival invariant holds under the process
    executor, with the identical fault schedule (pure in the seed)."""
    thread_res = run_chaos(seed=3, windows=10, workers=2).check()
    proc_res = run_chaos(seed=3, windows=10, workers=2,
                         executor="process").check()
    assert proc_res.faults == thread_res.faults
    assert proc_res.failed == thread_res.failed
    assert proc_res.report_text == thread_res.report_text


def test_process_workers_never_import_jax():
    """The spawn children run the jax-free core layer only, so none can
    initialize a backend and contend for the chip its parent holds."""
    tree = small_tree()
    pipe = AsyncAnalysisSession(tree, workers=2, executor=PROCESS)
    for s in straggler_stream(tree, 3):
        pipe.submit(s)
    probe = "'jax' in __import__('sys').modules"
    loaded = [pipe._proc_pool.submit(eval, probe).result(timeout=120)
              for _ in range(4)]
    pipe.close(timeout=120.0)
    assert loaded == [False] * 4
