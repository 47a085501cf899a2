"""Fault tolerance: deadlines, bounded admission, priority preemption
with warm-page resume, the numeric/kernel/fetch fault guards, the
seeded chaos harness (deterministic schedules, never-raises, per-step
invariant audits) and admission fairness under injected pool pressure."""

import warnings

import jax
import numpy as np
import pytest

from conftest import reference_decode
from repro import models as MZ
from repro.kernels import dispatch
from repro.models.config import LayerKind, ModelConfig
from repro.serving import (TERMINAL_STATUSES, ChaosConfig, ChaosMonkey,
                           Engine, RequestStatus, ServeConfig)
from repro.serving.chaos import AuditError, audit_engine
from repro.launch.mesh import make_mesh

TINY = ModelConfig(name="tiny", n_layers=2, d_model=64, vocab_size=512,
                   n_heads=4, n_kv_heads=2, d_ff=128, remat=False)

# paged + bucketed + prefix-shared: the geometry every fault path runs
# through.  16-token prompts fill exactly two pages (bucket 16), so
# preemption leaves two warm trie pages behind.
PAGED = dict(slots=2, max_len=64, prompt_pad=32, max_new_tokens=16,
             decode_chunk=2, eos_token=-1, page_size=8, prompt_buckets=8,
             prefix_cache=True, temperature=0.0)

PROMPT = np.arange(1, 17, dtype=np.int32)
PROMPT_HI = np.arange(20, 36, dtype=np.int32)


def mesh11():
    return make_mesh()


@pytest.fixture(scope="module")
def params():
    return MZ.init_model(jax.random.key(0), TINY)


@pytest.fixture(autouse=True)
def _clean_override():
    """Degraded mode flips a process-global dispatch override — never
    leak it across tests."""
    yield
    dispatch.set_mode_override(None)


def drain(eng, handles, max_steps=200):
    """Drive step() until every handle is terminal (bounded)."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        for _ in range(max_steps):
            eng.step()
            if all(h.done for h in handles):
                return
    raise AssertionError(
        f"not terminal after {max_steps} steps: "
        f"{[h.status.value for h in handles]}")


class TestDeadlinesAndRejection:
    def test_queued_deadline_times_out(self, params):
        eng = Engine(TINY, mesh11(), ServeConfig(**PAGED, num_pages=4),
                     params)
        blocker = eng.submit(PROMPT, max_new=12)
        doomed = eng.submit(PROMPT_HI, max_new=12, deadline_ms=0.01)
        eng.step()          # blocker admits; doomed waits past deadline
        eng.step()
        assert doomed.status is RequestStatus.TIMED_OUT
        assert doomed.tokens == []
        assert eng.stats().timeouts == 1
        drain(eng, [blocker])
        audit_engine(eng)

    def test_running_deadline_times_out_and_frees_pages(self, params):
        eng = Engine(TINY, mesh11(), ServeConfig(**PAGED, num_pages=4),
                     params)
        h = eng.submit(PROMPT, max_new=16, deadline_ms=0.01)
        eng.step()
        eng.step()          # past the deadline at this chunk boundary
        assert h.status is RequestStatus.TIMED_OUT
        b = eng._backend
        assert sum(b.slot_resv) == 0 and b.reserved == 0
        audit_engine(eng)

    def test_bounded_queue_rejects(self, params):
        eng = Engine(TINY, mesh11(),
                     ServeConfig(**PAGED, num_pages=8, max_queue=2),
                     params)
        hs = [eng.submit(PROMPT, max_new=2) for _ in range(3)]
        assert [h.status for h in hs] == [
            RequestStatus.QUEUED, RequestStatus.QUEUED,
            RequestStatus.REJECTED]
        assert hs[2].done and hs[2].tokens == []
        assert eng.stats().rejections == 1
        drain(eng, hs)
        assert hs[0].status is RequestStatus.DONE
        audit_engine(eng)

    def test_deadline_validation(self, params):
        eng = Engine(TINY, mesh11(), ServeConfig(**PAGED, num_pages=8),
                     params)
        with pytest.raises(ValueError, match="deadline_ms"):
            eng.submit(PROMPT, deadline_ms=0)


class TestPreemption:
    def test_high_priority_preempts_and_resumes_warm(self, params):
        """The tentpole end-to-end: a high-priority arrival under pool
        exhaustion evicts the low-priority slot; the victim's prompt
        pages stay warm (refcount zero) and its re-admission maps them
        (prefix hit, suffix-only prefill) — and the interrupted greedy
        stream is bit-identical to an uninterrupted run."""
        scfg = ServeConfig(**PAGED, num_pages=6)
        eng = Engine(TINY, mesh11(), scfg, params)
        lo = eng.submit(PROMPT, max_new=12)
        for _ in range(3):
            eng.step()
        assert len(lo.tokens) > 0
        pre_hits = eng.stats().prefix_hits
        hi = eng.submit(PROMPT_HI, max_new=12, priority=5)
        drain(eng, [lo, hi])
        st = eng.stats()
        assert st.preemptions == 1
        assert lo._req.preempts == 1
        assert [s.value for s in lo._req.history] == [
            "queued", "running", "preempted", "running", "done"]
        # warm resume: the re-admission hit the trie and mapped both
        # prompt pages read-only — only the suffix was recomputed
        assert st.prefix_hits == pre_hits + 1
        assert st.shared_pages >= 2
        assert lo.tokens == reference_decode(
            params, TINY, PROMPT, 12, -1, 16, 64)
        assert hi.tokens == reference_decode(
            params, TINY, PROMPT_HI, 12, -1, 16, 64)
        audit_engine(eng)

    def test_equal_priority_never_preempts(self, params):
        eng = Engine(TINY, mesh11(), ServeConfig(**PAGED, num_pages=4),
                     params)
        first = eng.submit(PROMPT, max_new=12)
        eng.step()
        second = eng.submit(PROMPT_HI, max_new=2)   # same priority (0)
        eng.step()
        assert first.status is RequestStatus.RUNNING
        assert second.status is RequestStatus.QUEUED
        assert eng.stats().preemptions == 0
        assert eng.stats().admission_waits > 0
        drain(eng, [first, second])


class TestNumericGuard:
    def test_nan_block_quarantines_only_affected_slot(self, params):
        """A poisoned fetched block must cost only the poisoned slot its
        chunk; the other slot's stream is untouched, the victim retries
        once on the ref plans, and no NaN ever reaches caller tokens."""
        scfg = ServeConfig(**PAGED, num_pages=10)
        eng = Engine(TINY, mesh11(), scfg, params)
        cfg = ChaosConfig(seed=0, rate=0.0, nan_rate=1.0,
                          audit_every_step=False)
        mk = ChaosMonkey(eng, cfg)
        a = eng.submit(PROMPT, max_new=6)
        b = eng.submit(PROMPT_HI, max_new=6)
        eng.step()          # both admitted + first chunk, fault-free
        mk.attach()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            eng.step()      # poisons one slot's column every tick
        mk.detach()
        st = eng.stats()
        assert st.numeric_faults == 1
        assert st.degraded and eng.degraded
        victim = a if a._req.faults else b
        assert victim._req.faults == 1
        assert RequestStatus.PREEMPTED in victim._req.history
        drain(eng, [a, b])
        # bit-exact despite the quarantine/retry (ref == compiled on CPU)
        assert a.tokens == reference_decode(
            params, TINY, PROMPT, 6, -1, 16, 64)
        assert b.tokens == reference_decode(
            params, TINY, PROMPT_HI, 6, -1, 16, 64)
        assert all(np.isfinite(t) for t in a.tokens + b.tokens)
        audit_engine(eng)

    def test_second_fault_fails_request(self, params):
        eng = Engine(TINY, mesh11(), ServeConfig(**PAGED, num_pages=10),
                     params)
        mk = ChaosMonkey(eng, ChaosConfig(
            seed=0, rate=0.0, nan_rate=1.0, audit_every_step=True))
        h = eng.submit(PROMPT, max_new=8)
        mk.attach()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            for _ in range(40):
                eng.step()
                if h.done:
                    break
        mk.detach()
        # nan_rate=1.0 with slots=2: the rng picks a slot per tick, so
        # the request is hit whenever its slot is drawn — two hits → FAILED
        assert h.status is RequestStatus.FAILED
        assert h._req.faults == 2
        assert eng.stats().numeric_faults == 2
        audit_engine(eng)


class TestKernelAndFetchFaults:
    def test_kernel_failure_degrades_and_retries(self, params):
        eng = Engine(TINY, mesh11(), ServeConfig(**PAGED, num_pages=10),
                     params)
        mk = ChaosMonkey(eng, ChaosConfig(seed=0, rate=0.0,
                                          kernel_rate=1.0))
        h = eng.submit(PROMPT, max_new=6)
        mk.attach()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            drain(eng, [h])
        mk.detach()
        st = eng.stats()
        assert st.kernel_failures >= 1
        assert st.degraded
        assert dispatch.mode_override() == "ref"
        assert h.status is RequestStatus.DONE
        assert h.tokens == reference_decode(
            params, TINY, PROMPT, 6, -1, 16, 64)

    def test_fetch_drop_is_retried_transparently(self, params):
        eng = Engine(TINY, mesh11(), ServeConfig(**PAGED, num_pages=10),
                     params)
        mk = ChaosMonkey(eng, ChaosConfig(seed=0, rate=0.0, drop_rate=1.0))
        h = eng.submit(PROMPT, max_new=6)
        mk.attach()
        drain(eng, [h])
        mk.detach()
        st = eng.stats()
        assert st.fetch_errors >= 1
        assert not st.degraded          # a retried fetch is not a fault
        assert h.status is RequestStatus.DONE
        assert h.tokens == reference_decode(
            params, TINY, PROMPT, 6, -1, 16, 64)


class TestChaosHarness:
    def _run(self, params, seed):
        dispatch.set_mode_override(None)
        scfg = ServeConfig(**PAGED, num_pages=10)
        eng = Engine(TINY, mesh11(), scfg, params)
        mk = ChaosMonkey(eng, ChaosConfig(seed=seed, rate=0.25)).attach()
        hs = [eng.submit(np.arange(1 + i, 17 + i, dtype=np.int32),
                         max_new=6) for i in range(4)]
        drain(eng, hs)
        mk.detach()
        return (mk.schedule, [h.status.value for h in hs],
                [h.tokens for h in hs])

    def test_same_seed_same_faults_same_outcome(self, params):
        """The acceptance bar: two runs at the same seed arm the same
        fault schedule, never raise out of step(), audit clean after
        every tick (audit_every_step defaults on), and land every
        request in the same terminal status with the same tokens."""
        s1, st1, t1 = self._run(params, seed=3)
        s2, st2, t2 = self._run(params, seed=3)
        assert s1 == s2 and len(s1) > 0
        assert st1 == st2 and t1 == t2
        assert all(s in {x.value for x in TERMINAL_STATUSES} for s in st1)

    def test_different_seed_different_schedule(self, params):
        s1, _, _ = self._run(params, seed=3)
        s2, _, _ = self._run(params, seed=4)
        assert s1 != s2

    def test_zero_rate_is_bit_identical_to_no_chaos(self, params):
        """An attached monkey at rate 0 must be a pure observer."""
        scfg = ServeConfig(**PAGED, num_pages=10)
        eng = Engine(TINY, mesh11(), scfg, params)
        ChaosMonkey(eng, ChaosConfig(seed=0, rate=0.0)).attach()
        h = eng.submit(PROMPT, max_new=8)
        drain(eng, [h])
        assert h.tokens == reference_decode(
            params, TINY, PROMPT, 8, -1, 16, 64)
        assert not eng.degraded

    def test_audit_flags_corruption(self, params):
        eng = Engine(TINY, mesh11(), ServeConfig(**PAGED, num_pages=10),
                     params)
        h = eng.submit(PROMPT, max_new=8)
        eng.step()
        audit_engine(eng)               # clean while running
        page = eng._backend.slot_pages[h.slot][0]
        eng._backend.free_pages.append(page)    # double-own one page
        with pytest.raises(AuditError, match="owned twice"):
            audit_engine(eng)
        eng._backend.free_pages.pop()
        drain(eng, [h])


class TestAdmissionFairness:
    """Satellite 3: fairness via the chaos pool-pressure injector."""

    def _pressured_engine(self, params):
        scfg = ServeConfig(**PAGED, num_pages=8)
        eng = Engine(TINY, mesh11(), scfg, params)
        mk = ChaosMonkey(eng, ChaosConfig(seed=0, rate=0.0)).attach()
        seized = mk.seize_pages(scfg.pool_pages)    # hold until released
        assert seized == scfg.pool_pages
        return eng, mk

    def test_fifo_among_equal_priority(self, params):
        eng, mk = self._pressured_engine(params)
        hs = [eng.submit(PROMPT, max_new=2) for _ in range(3)]
        for _ in range(3):
            eng.step()                  # fully blocked: nothing admits
        assert all(h.status is RequestStatus.QUEUED for h in hs)
        assert eng.stats().admission_waits > 0
        mk.release_pressure()
        order = []
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            for _ in range(100):
                for ev in eng.step():
                    if ev.final:
                        order.append(ev.uid)
                if all(h.done for h in hs):
                    break
        # submission order in, completion order out (equal budgets)
        assert order == [h.uid for h in hs]
        mk.detach()
        audit_engine(eng)

    def test_priority_jumps_queue(self, params):
        eng, mk = self._pressured_engine(params)
        lo = [eng.submit(PROMPT, max_new=2) for _ in range(2)]
        hi = eng.submit(PROMPT_HI, max_new=2, priority=3)
        eng.step()
        assert all(h.status is RequestStatus.QUEUED for h in lo + [hi])
        mk.release_pressure()
        eng.step()                      # slots refill: hi admits first
        # hi took a slot (may already be DONE: max_new fits one chunk);
        # the later-queued lo is the one left waiting
        assert hi.status is not RequestStatus.QUEUED
        assert lo[1].status is RequestStatus.QUEUED
        drain(eng, lo + [hi])
        assert hi._req.first_token_s <= min(
            h._req.first_token_s for h in lo)
        mk.detach()
        audit_engine(eng)


class TestDeadlineClock:
    """Satellite (PR 8): ``deadline_ms`` measures from the ORIGINAL
    arrival — neither preemption nor resumption restarts the clock, so
    a preempted-then-resumed request times out exactly when an
    uninterrupted one would."""

    def _preempt(self, eng, lo, hi_kw):
        """Step until pool pressure evicts ``lo`` for the new arrival."""
        hi = eng.submit(PROMPT_HI, priority=5, **hi_kw)
        for _ in range(20):
            if lo._req.preempts:
                break
            eng.step()
        assert lo._req.preempts == 1
        return hi

    def test_preempted_deadline_counts_from_original_arrival(self, params):
        eng = Engine(TINY, mesh11(), ServeConfig(**PAGED, num_pages=6),
                     params)
        lo = eng.submit(PROMPT, max_new=12, deadline_ms=60_000.0)
        for _ in range(3):
            eng.step()
        hi = self._preempt(eng, lo, dict(max_new=12))
        assert lo.status is RequestStatus.PREEMPTED
        # the wait in the preempted queue spends the SAME budget the
        # running phase did: age the one true clock past the deadline
        lo._req.arrival_s -= 61.0
        for _ in range(5):
            eng.step()
            if lo.done:
                break
        assert lo.status is RequestStatus.TIMED_OUT
        drain(eng, [hi])
        audit_engine(eng)

    def test_resumed_deadline_counts_from_original_arrival(self, params):
        """Preempt → resume → the request is RUNNING again, but its
        deadline still keys off the original arrival, not re-admission."""
        eng = Engine(TINY, mesh11(), ServeConfig(**PAGED, num_pages=6),
                     params)
        lo = eng.submit(PROMPT, max_new=16, deadline_ms=60_000.0)
        for _ in range(3):
            eng.step()
        hi = self._preempt(eng, lo, dict(max_new=2))
        drain(eng, [hi])                # frees pages; lo re-admits
        for _ in range(10):
            if lo.status is RequestStatus.RUNNING:
                break
            eng.step()
        assert lo.status is RequestStatus.RUNNING
        assert lo._req.preempts == 1
        lo._req.arrival_s -= 61.0       # older than its 60 s deadline
        for _ in range(5):
            eng.step()
            if lo.done:
                break
        assert lo.status is RequestStatus.TIMED_OUT
        drain(eng, [lo])
        audit_engine(eng)


class TestDegradedRecovery:
    """Satellite (PR 8): degraded mode is no longer one-way — after
    ``degraded_recover_chunks`` consecutive clean chunks the dispatch
    override clears, the backend re-traces onto the compiled plans and
    ``degraded_recoveries`` counts the round trip."""

    def _degrade(self, eng):
        mk = ChaosMonkey(eng, ChaosConfig(seed=0, rate=0.0,
                                          kernel_rate=1.0)).attach()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            for _ in range(5):
                eng.step()
                if eng.degraded:
                    break
        assert eng.degraded
        return mk

    def test_recovers_after_clean_chunks(self, params):
        scfg = ServeConfig(**PAGED, num_pages=10,
                           degraded_recover_chunks=3)
        eng = Engine(TINY, mesh11(), scfg, params)
        h = eng.submit(PROMPT, max_new=14)
        mk = self._degrade(eng)
        assert dispatch.mode_override() == "ref"
        mk.detach()                     # faults stop; chunks run clean
        drain(eng, [h])
        st = eng.stats()
        assert not st.degraded and not eng.degraded
        assert st.degraded_recoveries == 1
        assert dispatch.mode_override() is None
        # the ref detour and the re-trace never perturb the stream
        assert h.tokens == reference_decode(
            params, TINY, PROMPT, 14, -1, 16, 64)
        audit_engine(eng)

    def test_zero_threshold_stays_one_way(self, params):
        scfg = ServeConfig(**PAGED, num_pages=10,
                           degraded_recover_chunks=0)
        eng = Engine(TINY, mesh11(), scfg, params)
        h = eng.submit(PROMPT, max_new=14)
        mk = self._degrade(eng)
        mk.detach()
        drain(eng, [h])
        assert eng.degraded             # PR 7 behavior preserved
        assert eng.stats().degraded_recoveries == 0
        assert dispatch.mode_override() == "ref"

    def test_fault_during_probation_resets_streak(self, params):
        scfg = ServeConfig(**PAGED, num_pages=10,
                           degraded_recover_chunks=4)
        eng = Engine(TINY, mesh11(), scfg, params)
        h = eng.submit(PROMPT, max_new=16)
        mk = self._degrade(eng)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            eng.step()                  # still faulting: streak pinned
            eng.step()
        assert eng._clean_chunks == 0
        mk.detach()
        for _ in range(3):              # 3 clean < threshold 4
            eng.step()
        assert eng.degraded
        drain(eng, [h])                 # 4th clean chunk recovers
        assert not eng.degraded
        assert eng.stats().degraded_recoveries == 1


class TestChaosFamilies:
    """Satellite (PR 8): the chaos suite beyond the transformer LM —
    hybrid (SSM + shared attention) and encoder-decoder engines under
    injected faults, audited every step."""

    HY = ModelConfig(name="hy", n_layers=3, d_model=64, vocab_size=256,
                     n_heads=4, n_kv_heads=2, d_ff=128, remat=False,
                     layer_kinds=(LayerKind.MAMBA, LayerKind.SHARED_ATTN,
                                  LayerKind.MAMBA))
    ED = ModelConfig(name="ed", n_layers=2, n_encoder_layers=2,
                     d_model=64, vocab_size=256, n_heads=4, n_kv_heads=2,
                     d_ff=128, remat=False, is_encoder_decoder=True)
    SCFG = ServeConfig(slots=2, max_len=64, prompt_pad=16,
                       max_new_tokens=6, decode_chunk=2, eos_token=-1,
                       temperature=0.0)

    def _chaos_run(self, cfg, scfg):
        ps = MZ.init_model(jax.random.key(0), cfg)
        ref_eng = Engine(cfg, mesh11(), scfg, ps)
        ref_hs = [ref_eng.submit(PROMPT), ref_eng.submit(PROMPT_HI)]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            ref_eng.run()
        ref = [h.tokens for h in ref_hs]
        eng = Engine(cfg, mesh11(), scfg, ps)
        mk = ChaosMonkey(eng, ChaosConfig(
            seed=5, rate=0.0, drop_rate=0.3, kernel_rate=0.3,
            audit_every_step=True)).attach()
        hs = [eng.submit(PROMPT), eng.submit(PROMPT_HI)]
        drain(eng, hs)
        mk.detach()
        assert all(h.status is RequestStatus.DONE for h in hs)
        # drop/kernel faults are transparent: fetch retries and the ref
        # detour never perturb the greedy stream
        assert [h.tokens for h in hs] == ref
        audit_engine(eng)

    def test_hybrid_chaos_audits_clean(self):
        self._chaos_run(self.HY, self.SCFG)

    def test_encdec_chaos_audits_clean(self):
        self._chaos_run(self.ED, self.SCFG)

    def test_hybrid_ssm_state_preempt_resume_parity(self):
        """Preempt a hybrid request mid-decode and resume it: the SSM
        recurrent state lives outside the paged KV pool and is rebuilt
        by the resume re-prefill — the continued greedy stream must be
        bit-identical to an uninterrupted run."""
        scfg = ServeConfig(slots=2, max_len=64, prompt_pad=32,
                           max_new_tokens=12, decode_chunk=2,
                           eos_token=-1, temperature=0.0, page_size=8,
                           prompt_buckets=8, num_pages=6)
        ps = MZ.init_model(jax.random.key(0), self.HY)
        ref_eng = Engine(self.HY, mesh11(), scfg, ps)
        ref_lo = ref_eng.submit(PROMPT)
        ref_hi = ref_eng.submit(PROMPT_HI, max_new=12)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            ref_eng.run()
        eng = Engine(self.HY, mesh11(), scfg, ps)
        lo = eng.submit(PROMPT)
        for _ in range(3):
            eng.step()
        assert len(lo.tokens) > 0
        hi = eng.submit(PROMPT_HI, max_new=12, priority=5)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            drain(eng, [lo, hi])
        assert lo._req.preempts == 1
        assert lo.tokens == ref_lo.tokens
        assert hi.tokens == ref_hi.tokens
        audit_engine(eng)
