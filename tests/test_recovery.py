"""Crash safety: the write-ahead journal, engine snapshot/restore, and
the supervised kill-and-recover guarantee — a crashed engine restored
from journal + snapshot finishes every request with a greedy transcript
bit-identical to an uninterrupted run, with no duplicated or dropped
streamed tokens and a clean audit."""

import json
import time
import warnings

import jax
import numpy as np
import pytest

from repro import models as MZ
from repro.core.sparse_linear import SparsityConfig, pack_params
from repro.models.config import ModelConfig
from repro.serving import (ChaosConfig, ChaosCrashError, ChaosMonkey,
                           Engine, Journal, RequestStatus, ServeConfig,
                           Supervisor, SupervisorError)
from repro.launch.mesh import make_mesh

TINY = ModelConfig(name="tiny", n_layers=2, d_model=64, vocab_size=512,
                   n_heads=4, n_kv_heads=2, d_ff=128, remat=False)
NM_TINY = ModelConfig(name="tiny-nm", n_layers=2, d_model=128,
                      vocab_size=512, n_heads=4, n_kv_heads=2, d_ff=256,
                      remat=False,
                      mlp_sparsity=SparsityConfig(format="nm", n=2, m=4,
                                                  block_n=64))

# three requests over two slots: the third rides the queue across the
# crash, so recovery re-queues both an in-flight and a never-admitted
# request
PROMPTS = [np.arange(1, 9, dtype=np.int32),
           np.arange(20, 30, dtype=np.int32),
           np.arange(40, 44, dtype=np.int32)]

BASE = dict(slots=2, max_len=64, prompt_pad=16, max_new_tokens=8,
            decode_chunk=2, eos_token=-1, temperature=0.0)
KINDS = {
    "mono": {},
    "paged": dict(page_size=8, prompt_buckets=8),
    "spec": dict(spec_k=2, spec_draft="self"),
}


def mesh11():
    return make_mesh()


@pytest.fixture(scope="module")
def params():
    return MZ.init_model(jax.random.key(0), TINY)


@pytest.fixture(scope="module")
def nm_params():
    return pack_params(MZ.init_model(jax.random.key(0), NM_TINY), NM_TINY)


def scfg_of(kind, jp=""):
    return ServeConfig(**BASE, **KINDS[kind], journal_path=jp)


def reference_transcripts(cfg, params, kind):
    """The uninterrupted run every recovery must reproduce bit-exactly."""
    eng = Engine(cfg, mesh11(), scfg_of(kind), params)
    hs = [eng.submit(p) for p in PROMPTS]
    eng.run()
    return [h.tokens for h in hs]


class TestJournal:
    def test_mirror_round_trips_a_run(self, params, tmp_path):
        jp = str(tmp_path / "j.jsonl")
        eng = Engine(TINY, mesh11(), scfg_of("mono", jp), params)
        hs = [eng.submit(p) for p in PROMPTS]
        eng.run()
        eng.journal.close()
        mirror = Journal(jp).state
        assert set(mirror.reqs) == {0, 1, 2}
        for h in hs:
            jr = mirror.reqs[h.uid]
            assert jr.out == h.tokens
            assert jr.status == "done"
            assert jr.rows0 == h._req.rows0
            assert jr.prompt == [int(x) for x in h._req.prompt]
        assert mirror.tick == eng._tick
        assert mirror.scfg["max_new_tokens"] == 8
        assert mirror.next_uid == 3

    def test_torn_tail_is_tolerated(self, params, tmp_path):
        jp = str(tmp_path / "j.jsonl")
        eng = Engine(TINY, mesh11(), scfg_of("mono", jp), params)
        eng.submit(PROMPTS[0])
        for _ in range(2):
            eng.step()
        eng.journal.close()
        with open(jp, "a") as f:        # a crash mid-write tears a line
            f.write('{"t": "commit", "uid": 0, "of')
        mirror = Journal(jp).state
        assert 0 in mirror.reqs         # everything before the tear holds
        assert len(mirror.reqs[0].out) > 0

    def test_submit_is_durable_before_first_step(self, params, tmp_path):
        jp = str(tmp_path / "j.jsonl")
        eng = Engine(TINY, mesh11(), scfg_of("mono", jp), params)
        eng.submit(PROMPTS[0], priority=3, deadline_ms=5000.0)
        # no step(), no close(): the submit record must already be on disk
        with open(jp) as f:
            recs = [json.loads(line) for line in f]
        assert [r["t"] for r in recs] == ["cfg", "submit"]
        assert recs[1]["prio"] == 3
        assert recs[1]["deadline_ms"] == 5000.0

    def test_rejected_submission_journals_terminal(self, params, tmp_path):
        jp = str(tmp_path / "j.jsonl")
        scfg = ServeConfig(**BASE, max_queue=1, journal_path=jp)
        eng = Engine(TINY, mesh11(), scfg, params)
        eng.submit(PROMPTS[0])
        h = eng.submit(PROMPTS[1])      # bounced off the bounded queue
        assert h.status is RequestStatus.REJECTED
        eng.journal.close()
        mirror = Journal(jp).state
        assert mirror.reqs[h.uid].status == "rejected"


class TestSnapshotRestore:
    def test_journal_only_restore_is_bit_identical(self, params, tmp_path):
        ref = reference_transcripts(TINY, params, "paged")
        jp = str(tmp_path / "j.jsonl")
        eng = Engine(TINY, mesh11(), scfg_of("paged", jp), params)
        hs = [eng.submit(p) for p in PROMPTS]
        for _ in range(2):
            eng.step()
        pre = [list(h.tokens) for h in hs]
        assert any(pre)                 # tokens were delivered pre-crash
        # abandon the engine (no close, no extra flush) and recover from
        # the journal alone — scfg round-trips from the cfg header
        rec = Engine.restore(TINY, mesh11(), params, journal_path=jp)
        assert rec.engine.scfg.page_size == 8
        rec.engine.run()
        got = [rec.handles[i].tokens for i in range(3)]
        assert got == ref
        for i, p in enumerate(pre):     # delivered tokens never re-emitted
            assert got[i][: len(p)] == p
        rec.engine.audit()

    def test_snapshot_plus_tail_restore(self, params, tmp_path):
        ref = reference_transcripts(TINY, params, "mono")
        jp, sd = str(tmp_path / "j.jsonl"), str(tmp_path / "snap")
        eng = Engine(TINY, mesh11(), scfg_of("mono", jp), params)
        hs = [eng.submit(p) for p in PROMPTS]
        eng.step()
        eng.snapshot(sd)                # snapshot, then one more tick of
        eng.step()                      # journal tail past it
        rec = Engine.restore(TINY, mesh11(), params, journal_path=jp,
                             snapshot_dir=sd)
        e2 = rec.engine
        assert e2._tick == eng._tick    # the tail wins over the snapshot
        assert {r.uid for r in e2.queue} == {0, 1, 2}
        for r in e2.queue:
            src = next(h._req for h in hs if h.uid == r.uid)
            assert r.out == src.out
            assert r.rows0 == src.rows0
            assert r.status is (RequestStatus.PREEMPTED if r.rows0
                                is not None else RequestStatus.QUEUED)
        assert rec.timings["load_ms"] >= 0.0
        e2.run()
        assert [rec.handles[i].tokens for i in range(3)] == ref
        e2.audit()

    def test_stats_and_uid_counter_survive(self, params, tmp_path):
        jp, sd = str(tmp_path / "j.jsonl"), str(tmp_path / "snap")
        eng = Engine(TINY, mesh11(), scfg_of("mono", jp), params)
        [eng.submit(p) for p in PROMPTS]
        for _ in range(3):
            eng.step()
        eng.snapshot(sd)
        prefills = eng._stats["prefills"]
        rec = Engine.restore(TINY, mesh11(), params, journal_path=jp,
                             snapshot_dir=sd)
        assert rec.engine._stats["prefills"] == prefills
        assert rec.engine._uid_next == 3    # new uids never collide
        h = rec.engine.submit(PROMPTS[0])
        assert h.uid == 3


class TestKillAndRecover:
    """The acceptance property: seeded mid-wave crash + supervised
    restore is invisible in the transcript, for every backend kind and
    both weight packs."""

    @pytest.mark.parametrize("kind", ["mono", "paged", "spec"])
    @pytest.mark.parametrize("pack", ["dense", "nm"])
    def test_crash_mid_wave_bit_identical(self, params, nm_params,
                                          tmp_path, kind, pack):
        cfg, p = ((TINY, params) if pack == "dense"
                  else (NM_TINY, nm_params))
        ref = reference_transcripts(cfg, p, kind)
        jp, sd = str(tmp_path / "j.jsonl"), str(tmp_path / "snap")
        sup = Supervisor(cfg, mesh11(), scfg_of(kind), p,
                         journal_path=jp, snapshot_dir=sd,
                         snapshot_every=2)
        ChaosMonkey(sup.engine,
                    ChaosConfig(seed=7, rate=0.0, crash_tick=2)).attach()
        hs = [sup.submit(q) for q in PROMPTS]
        events = []
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            for _ in range(60):
                events += sup.step()
                if all(h.done for h in hs):
                    break
        assert sup.restarts == 1
        assert [h.tokens for h in hs] == ref
        # streamed-event dedup: across the crash, each request's event
        # indices are exactly 0..n-1, each exactly once
        for h in hs:
            idx = [ev.index for ev in events if ev.uid == h.uid]
            assert idx == list(range(len(h.tokens)))
        sup.audit()
        st = sup.stats()
        assert st.restarts == 1
        assert sup.last_recovery["total_ms"] > 0.0

    def test_handle_iteration_streams_through_crash(self, params,
                                                    tmp_path):
        ref = reference_transcripts(TINY, params, "mono")
        jp = str(tmp_path / "j.jsonl")
        sup = Supervisor(TINY, mesh11(), scfg_of("mono"), params,
                         journal_path=jp)
        ChaosMonkey(sup.engine,
                    ChaosConfig(seed=0, rate=0.0, crash_tick=1)).attach()
        hs = [sup.submit(q) for q in PROMPTS]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            streamed = [t for t in hs[0]]   # blocks through the crash
            sup.run()
        assert sup.restarts == 1
        assert streamed == ref[0]
        assert [h.tokens for h in hs] == ref

    def test_restart_cap_raises(self, params, tmp_path):
        jp = str(tmp_path / "j.jsonl")
        sup = Supervisor(TINY, mesh11(), scfg_of("mono"), params,
                         journal_path=jp, max_restarts=1)

        def always_crash():
            raise ChaosCrashError("wedged for good")

        sup.engine.step = always_crash
        sup.submit(PROMPTS[0])
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            sup.step()                  # restart 1: tolerated
            sup.engine.step = always_crash
            with pytest.raises(SupervisorError):
                sup.step()              # restart 2: past the cap
        assert sup.restarts == 2

    def test_supervisor_requires_journal(self, params):
        with pytest.raises(ValueError, match="journal_path"):
            Supervisor(TINY, mesh11(), scfg_of("mono"), params,
                       journal_path="")


class TestWatchdog:
    def test_hang_trips_watchdog_and_recovers(self, params, tmp_path):
        ref = reference_transcripts(TINY, params, "mono")
        jp = str(tmp_path / "j.jsonl")
        sup = Supervisor(TINY, mesh11(), scfg_of("mono"), params,
                         journal_path=jp, watchdog_ms=50.0)
        ChaosMonkey(sup.engine,
                    ChaosConfig(seed=0, rate=0.0, hang_tick=1,
                                hang_s=0.25)).attach()
        hs = [sup.submit(q) for q in PROMPTS]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            sup.run()
        assert sup.restarts == 1        # wedged device → one restore
        assert [h.tokens for h in hs] == ref
        sup.audit()

    def test_grace_period_tolerates_slow_first_steps(self, params,
                                                     tmp_path):
        jp = str(tmp_path / "j.jsonl")
        # an absurdly tight budget: compilation alone would trip it, so
        # only the grace window keeps a healthy engine alive
        sup = Supervisor(TINY, mesh11(), scfg_of("mono"), params,
                         journal_path=jp, watchdog_ms=1e-6)
        sup.submit(PROMPTS[0], max_new=2)
        sup.step()                      # compile tick: grace, no restart
        assert sup.restarts == 0


class TestPrefixPinsAcrossRestart:
    def test_pins_survive_and_rebind(self, params, tmp_path):
        paged = dict(BASE, page_size=8, prompt_buckets=8,
                     prefix_cache=True, prompt_pad=32, max_len=96)
        head = np.arange(1, 17, dtype=np.int32)     # two pinned pages
        tails = [np.arange(60, 68, dtype=np.int32),
                 np.arange(70, 78, dtype=np.int32)]
        ref_eng = Engine(TINY, mesh11(), ServeConfig(**paged), params)
        rh = ref_eng.register_prefix(head)
        ref_hs = [ref_eng.submit(t, prefix=rh) for t in tails]
        ref_eng.run()
        ref = [h.tokens for h in ref_hs]
        jp = str(tmp_path / "j.jsonl")
        sup = Supervisor(TINY, mesh11(), ServeConfig(**paged), params,
                         journal_path=jp)
        ChaosMonkey(sup.engine,
                    ChaosConfig(seed=0, rate=0.0, crash_tick=1)).attach()
        ph = sup.register_prefix(head)
        hs = [sup.submit(t, prefix=ph) for t in tails]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            sup.run()
        assert sup.restarts == 1
        assert [h.tokens for h in hs] == ref
        assert not ph.released and ph.n_pages == 2
        rep = sup.audit()
        assert rep["journal_pins"] == 1
        ph.release()                    # the re-bound handle still works
        assert ph.released
        sup.audit()


class TestDeadlineAcrossRestart:
    def test_deadline_keeps_ticking_through_recovery(self, params,
                                                     tmp_path):
        """Satellite: deadline_ms measures from the ORIGINAL wall-clock
        arrival — downtime between crash and restore still counts, so a
        restored request times out exactly when an uninterrupted one
        would (not ``deadline_ms`` after re-admission)."""
        jp = str(tmp_path / "j.jsonl")
        eng = Engine(TINY, mesh11(), scfg_of("mono", jp), params)
        h = eng.submit(PROMPTS[0], deadline_ms=120.0)
        eng.step()
        assert len(h.tokens) >= 0 and not h.done
        # the process dies; the outage outlives the deadline
        time.sleep(0.15)
        rec = Engine.restore(TINY, mesh11(), params, journal_path=jp)
        r = rec.engine.queue[0]
        assert r.deadline_ms == 120.0
        rec.engine.step()               # first tick enforces the clock
        assert rec.handles[h.uid].status is RequestStatus.TIMED_OUT
