"""The jitted serving programs: prefill steps and decode loops.

Three program families make up the hot path:

  * :func:`build_prefill_slot_step` — prefill ONE request into slot
    ``i`` of the shared cache and stamp the slot's decode state (first
    token, position, budget) on-device.  Refill never drains the batch.
    With ``paged=True`` the scratch cache shares the page pool and the
    slot's host-assigned pages ride in as an argument.
  * :func:`build_decode_loop` — a ``lax.scan`` that runs
    ``decode_chunk`` decode+sample steps fully on-device, carrying the
    whole per-slot decode state plus a per-slot temperature vector; EOS,
    budget exhaustion and cache capacity are all detected in-scan.  The
    host sees one ``(decode_chunk, slots)`` token block per call: **one
    device→host sync per chunk**.  ``paged=True`` threads the
    host-authoritative page table in (host→device only) and narrows the
    attention gather to ``view_pages``.
  * :func:`build_spec_decode_loop` — the speculative twin: each scan
    step drafts ``spec_k`` tokens per slot with the draft params, runs
    ONE batched dense verify over the ``(slots, spec_k+1)`` block, and
    commits the accepted prefix (greedy token match, or lossless
    residual rejection sampling at temperature > 0).  One builder serves
    both cache layouts — the backend picks ``paged``/``view_pages``.

``build_prefill_step`` / ``build_decode_step`` are the wave-style
whole-batch steps, kept for the dry-run's cells and as the 1-token
reference the benchmarks compare against.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import models as MZ
from repro.distributed import sharding as SH
from repro.models.config import ModelConfig
from repro.serving.config import ServeConfig
from repro.serving.state import (_slot_uniform, sample_token_folded,
                                 sample_token_slots)


def _state_shardings(mesh: Mesh) -> Dict[str, NamedSharding]:
    """Replicated shardings for the per-slot decode state.

    Explicit (not ``None``/unspecified) so the first call — whose state
    comes fresh off the host — and every later call — whose state is a
    committed device output — hit the SAME compiled executable instead
    of forking a second variant mid-serve."""
    return {k: NamedSharding(mesh, P())
            for k in ("tok", "pos", "done", "left")}


def build_prefill_step(cfg: ModelConfig, mesh: Mesh, scfg: ServeConfig,
                       abstract_params: Any, abstract_cache: Any,
                       batch_shapes: Dict[str, Any]) -> Callable:
    """(params, batch, cache) → (last_logits, cache).

    Whole-batch wave prefill — what the dry-run's ``prefill_*`` cells
    lower.  The engine itself prefills per slot (see
    :func:`build_prefill_slot_step`).
    """
    pspecs = SH.param_specs(abstract_params, cfg, mesh)
    cspecs = SH.cache_specs(abstract_cache, cfg, mesh, kv_mode=scfg.kv_mode)
    bspecs = SH.batch_specs(batch_shapes, mesh)

    def step(params, batch, cache):
        return MZ.prefill(params, cfg, batch, cache)

    return jax.jit(
        step,
        in_shardings=(SH.named(mesh, pspecs), SH.named(mesh, bspecs),
                      SH.named(mesh, cspecs)),
        out_shardings=(None, SH.named(mesh, cspecs)),
        donate_argnums=(2,))


def build_decode_step(cfg: ModelConfig, mesh: Mesh, scfg: ServeConfig,
                      abstract_params: Any, abstract_cache: Any) -> Callable:
    """(params, token (B,), cache, pos () or (B,)) → (logits, cache).

    One decode step; the per-token loop the benchmarks use as the seed
    reference.  ``pos`` may be per-slot (vector) — the model layer
    handles both.
    """
    pspecs = SH.param_specs(abstract_params, cfg, mesh)
    cspecs = SH.cache_specs(abstract_cache, cfg, mesh, kv_mode=scfg.kv_mode)

    def step(params, token, cache, pos):
        return MZ.decode_step(params, cfg, token, cache, pos)

    return jax.jit(
        step,
        in_shardings=(SH.named(mesh, pspecs), None,
                      SH.named(mesh, cspecs), None),
        out_shardings=(None, SH.named(mesh, cspecs)),
        donate_argnums=(2,))


def build_prefill_slot_step(cfg: ModelConfig, mesh: Mesh, scfg: ServeConfig,
                            abstract_params: Any, abstract_cache: Any,
                            prompt_rows: Optional[int] = None,
                            paged: bool = False) -> Callable:
    """(params, tokens (1, P), cache, state, slot, budget, temp, key
    [, page_row (max_pages,)]) → (cache, state).

    Prefills one request into a fresh batch-1 scratch cache, merges it
    into slot ``slot`` of the shared cache, samples the first token from
    the prompt logits (at the request's own traced ``temp``) and stamps
    the slot's decode state — all on-device (the first token is emitted
    by the next decode chunk, so refill costs zero host syncs).
    ``slot`` is a traced scalar: one compile serves every slot.

    ``paged=True``: the scratch cache *shares* the page pool
    (``blank_slot_cache``) and gets the slot's host-assigned pages
    stamped into its table, so prefill scatters the prompt straight into
    pages no live slot owns; the merge then only writes the slot's
    page-table row.  ``prompt_rows`` is static — with ``prompt_buckets``
    enabled the backend compiles one step per bucket and short prompts
    stop paying full-``prompt_pad`` prefill work.
    """
    rows = prompt_rows or scfg.prompt_pad
    pspecs = SH.param_specs(abstract_params, cfg, mesh)
    cspecs = SH.cache_specs(abstract_cache, cfg, mesh, kv_mode=scfg.kv_mode)
    bspecs = SH.batch_specs(
        {"tokens": jax.ShapeDtypeStruct((1, rows), jnp.int32)}, mesh)

    def prefill(params, batch, cache, state, slot, budget, temp, key,
                page_row=None):
        scratch = MZ.blank_slot_cache(cache)
        if paged:
            scratch = MZ.set_page_table(scratch, page_row[None])
        logits, scratch = MZ.prefill(params, cfg, batch, scratch)
        cache = MZ.merge_cache_slot(cache, scratch, slot)
        first = sample_token_slots(logits[:, :cfg.vocab_size], key,
                                   temp[None])[0]
        state = {
            "tok": state["tok"].at[slot].set(first),
            "pos": state["pos"].at[slot].set(rows),
            "done": state["done"].at[slot].set(False),
            "left": state["left"].at[slot].set(budget),
        }
        return cache, state

    sspecs = _state_shardings(mesh)
    extra = (None,) if paged else ()
    # the jitted function's name is the program's name in a profile
    # (``jit_prefill_slot``), apart from every other serving program
    if paged:
        def prefill_slot(params, batch, cache, state, slot, budget, temp,
                         key, page_row):
            return prefill(params, batch, cache, state, slot, budget,
                           temp, key, page_row)
    else:
        def prefill_slot(params, batch, cache, state, slot, budget, temp,
                         key):
            return prefill(params, batch, cache, state, slot, budget,
                           temp, key)

    return jax.jit(
        prefill_slot,
        in_shardings=(SH.named(mesh, pspecs), SH.named(mesh, bspecs),
                      SH.named(mesh, cspecs), sspecs, None, None, None,
                      None) + extra,
        out_shardings=(SH.named(mesh, cspecs), sspecs),
        donate_argnums=(2, 3))


def build_prefix_prefill_slot_step(cfg: ModelConfig, mesh: Mesh,
                                   scfg: ServeConfig, abstract_params: Any,
                                   abstract_cache: Any, prompt_rows: int,
                                   start: int, cow: bool = False
                                   ) -> Callable:
    """(params, tokens (1, rows-start), cache, state, slot, budget, temp,
    key, page_row[, copy_src, copy_dst]) → (cache, state).

    The prefix-sharing twin of :func:`build_prefill_slot_step`: rows
    ``[0, start)`` of the prompt are already resident in shared pages
    mapped read-only into ``page_row``, so only the suffix is computed —
    a ``models.decode_block`` forward at per-slot position ``start``
    (the same multi-token decode-shaped path the speculative verify
    runs, which is bit-exact against full prefill on the greedy stream).
    The suffix scatter lands entirely in the slot's private pages
    (positions ≥ ``start``); the shared head is only ever *gathered*.

    ``cow=True`` first device-copies ``copy_src`` → ``copy_dst`` (both
    traced page ids): the divergent page's common row prefix rides in
    via the copy, the rows past it are overwritten by the suffix scatter
    or dead by kv-length masking.  ``start`` is static — one compile per
    (rows, start, cow) admission shape, same cache discipline as the
    prompt buckets.
    """
    rows, span = prompt_rows, prompt_rows - start
    pspecs = SH.param_specs(abstract_params, cfg, mesh)
    cspecs = SH.cache_specs(abstract_cache, cfg, mesh, kv_mode=scfg.kv_mode)
    bspecs = SH.batch_specs(
        {"tokens": jax.ShapeDtypeStruct((1, span), jnp.int32)}, mesh)
    sspecs = _state_shardings(mesh)

    def prefill(params, batch, cache, state, slot, budget, temp, key,
                page_row, copy_src=None, copy_dst=None):
        if cow:
            cache = MZ.copy_page(cache, copy_src, copy_dst)
        scratch = MZ.blank_slot_cache(cache)
        scratch = MZ.set_page_table(scratch, page_row[None])
        logits, scratch, _ = MZ.decode_block(
            params, cfg, batch["tokens"], scratch,
            jnp.full((1,), start, jnp.int32))
        cache = MZ.merge_cache_slot(cache, scratch, slot)
        first = sample_token_slots(logits[:, -1, :cfg.vocab_size], key,
                                   temp[None])[0]
        state = {
            "tok": state["tok"].at[slot].set(first),
            "pos": state["pos"].at[slot].set(rows),
            "done": state["done"].at[slot].set(False),
            "left": state["left"].at[slot].set(budget),
        }
        return cache, state

    if cow:
        def step(params, batch, cache, state, slot, budget, temp, key,
                 page_row, copy_src, copy_dst):
            return prefill(params, batch, cache, state, slot, budget,
                           temp, key, page_row, copy_src, copy_dst)
        extra = (None, None, None)
    else:
        def step(params, batch, cache, state, slot, budget, temp, key,
                 page_row):
            return prefill(params, batch, cache, state, slot, budget,
                           temp, key, page_row)
        extra = (None,)

    return jax.jit(
        step,
        in_shardings=(SH.named(mesh, pspecs), SH.named(mesh, bspecs),
                      SH.named(mesh, cspecs), sspecs, None, None, None,
                      None) + extra,
        out_shardings=(SH.named(mesh, cspecs), sspecs),
        donate_argnums=(2, 3))


def build_prefix_fill_step(cfg: ModelConfig, mesh: Mesh, scfg: ServeConfig,
                           abstract_params: Any, abstract_cache: Any,
                           prompt_rows: int) -> Callable:
    """(params, tokens (1, rows), cache, page_row) → cache.

    ``Engine.register_prefix``'s fill: prefill the registered head into
    the pages ``page_row`` names, touching no slot's page table or
    decode state — the scratch shares the pool, the logits are
    discarded, and the full cache keeps its own tables
    (:func:`models.unpage_view` adopts only the updated pools).  Blocks
    the head already had resident are rewritten with bit-identical
    values (same tokens, same positions), so re-registering is safe.
    """
    pspecs = SH.param_specs(abstract_params, cfg, mesh)
    cspecs = SH.cache_specs(abstract_cache, cfg, mesh, kv_mode=scfg.kv_mode)
    bspecs = SH.batch_specs(
        {"tokens": jax.ShapeDtypeStruct((1, prompt_rows), jnp.int32)}, mesh)

    def step(params, batch, cache, page_row):
        scratch = MZ.blank_slot_cache(cache)
        scratch = MZ.set_page_table(scratch, page_row[None])
        _, scratch = MZ.prefill(params, cfg, batch, scratch)
        return MZ.unpage_view(scratch, cache)

    return jax.jit(
        step,
        in_shardings=(SH.named(mesh, pspecs), SH.named(mesh, bspecs),
                      SH.named(mesh, cspecs), None),
        out_shardings=SH.named(mesh, cspecs),
        donate_argnums=(2,))


def build_prefill_wave_step(cfg: ModelConfig, mesh: Mesh, scfg: ServeConfig,
                            abstract_params: Any, abstract_cache: Any
                            ) -> Callable:
    """(params, tokens (slots, P), cache, valid, budgets, temps, key)
    → (cache, state).

    The cold-start / wave-boundary fast path: when EVERY slot is free the
    whole batch prefills in one call (per-slot prefill would pay ``slots``
    jit dispatches for the same rows) and the decode state is rebuilt
    wholesale — ``valid`` masks slots that actually received a request.
    Never used while any slot is live: whole-batch prefill rewrites every
    slot's cache rows.
    """
    pspecs = SH.param_specs(abstract_params, cfg, mesh)
    cspecs = SH.cache_specs(abstract_cache, cfg, mesh, kv_mode=scfg.kv_mode)
    bspecs = SH.batch_specs(
        {"tokens": jax.ShapeDtypeStruct((scfg.slots, scfg.prompt_pad),
                                        jnp.int32)}, mesh)
    sspecs = _state_shardings(mesh)

    def step(params, batch, cache, valid, budgets, temps, key):
        logits, cache = MZ.prefill(params, cfg, batch, cache)
        first = sample_token_slots(logits[:, :cfg.vocab_size], key, temps)
        state = {
            "tok": jnp.where(valid, first, 0),
            "pos": jnp.where(valid, scfg.prompt_pad, 0).astype(jnp.int32),
            "done": ~valid,
            "left": jnp.where(valid, budgets, 0),
        }
        return cache, state

    return jax.jit(
        step,
        in_shardings=(SH.named(mesh, pspecs), SH.named(mesh, bspecs),
                      SH.named(mesh, cspecs), None, None, None, None),
        out_shardings=(SH.named(mesh, cspecs), sspecs),
        donate_argnums=(2,))


def build_decode_loop(cfg: ModelConfig, mesh: Mesh, scfg: ServeConfig,
                      abstract_params: Any, abstract_cache: Any,
                      paged: bool = False,
                      view_pages: Optional[int] = None) -> Callable:
    """(params, cache, state, temps, key[, ptab])
    → (cache, state, tokens, emitted).

    Runs ``scfg.decode_chunk`` decode+sample steps on-device in one
    ``lax.scan``.  Each step first *emits* the carry token (the one
    sampled last step — or by the slot's prefill), then decides whether
    the slot is finished (EOS, budget, or cache capacity) and, if not,
    decodes+samples the next token at the slot's own position and
    temperature (``temps`` is a traced per-slot vector; 0 → greedy).
    Finished and free slots ride along masked: their state is frozen and
    their (idempotent) cache writes land on rows nothing attends to.

    ``paged=True``: the host-authoritative page table rides in as an
    argument (host→device only — the one-device-fetch-per-chunk contract
    is untouched) and is stamped into the cache before the scan, so page
    allocations and slot retirements made between chunks take effect
    here.  ``view_pages`` (static) narrows the attention gather to the
    first N logical pages — the backend picks the smallest bucket
    covering every live slot, so decode attention work tracks actual
    sequence lengths.  Writes from frozen (done/free) slots whose
    position lies beyond the view clip into the slot's page-table tail,
    which retirement has nulled — they land in the garbage page.

    Returns the new cache/state plus ``tokens``/``emitted`` blocks of
    shape ``(decode_chunk, slots)`` — the single host transfer per chunk.
    """
    pspecs = SH.param_specs(abstract_params, cfg, mesh)
    cspecs = SH.cache_specs(abstract_cache, cfg, mesh, kv_mode=scfg.kv_mode)
    V = cfg.vocab_size

    def scan_chunk(params, cache, state, temps, key):
        def body(carry, step):
            cache, st, key = carry
            tok, pos = st["tok"], st["pos"]
            done, left = st["done"], st["left"]
            emit = (~done) & (left > 0)
            left = left - emit.astype(left.dtype)
            # the slot is finished once the emitted token is EOS, the
            # budget is spent, or the cache can't hold another row
            done = done | (emit & ((tok == scfg.eos_token) | (left == 0)
                                   | (pos + 1 >= scfg.max_len)))
            if paged:
                with jax.named_scope("kv_cache"):
                    vcache = MZ.page_view(cache, view_pages)
                logits, vcache = MZ.decode_step(params, cfg, tok, vcache,
                                                pos)
                with jax.named_scope("kv_cache"):
                    cache = MZ.unpage_view(vcache, cache)
            else:
                logits, cache = MZ.decode_step(params, cfg, tok, cache, pos)
            nxt = sample_token_slots(logits[:, :V],
                                     jax.random.fold_in(key, step), temps)
            alive = ~done
            st = {"tok": jnp.where(alive, nxt, tok),
                  "pos": jnp.where(alive, pos + 1, pos),
                  "done": done, "left": left}
            return (cache, st, key), (tok, emit)

        (cache, state, _), (tokens, emitted) = jax.lax.scan(
            body, (cache, state, key), jnp.arange(scfg.decode_chunk))
        return cache, state, tokens, emitted

    sspecs = _state_shardings(mesh)
    if paged:
        def loop(params, cache, state, temps, key, ptab):
            with jax.named_scope("kv_cache"):
                cache = MZ.set_page_table(cache, ptab)
            return scan_chunk(params, cache, state, temps, key)
    else:
        def loop(params, cache, state, temps, key):
            return scan_chunk(params, cache, state, temps, key)

    extra = (None,) if paged else ()
    # the fetched token block is pinned FULLY REPLICATED: the one host
    # sync per chunk reads it without a cross-shard gather, on any mesh
    rep = NamedSharding(mesh, P())
    return jax.jit(
        loop,
        in_shardings=(SH.named(mesh, pspecs), SH.named(mesh, cspecs),
                      sspecs, None, None) + extra,
        out_shardings=(SH.named(mesh, cspecs), sspecs, rep, rep),
        donate_argnums=(1, 2))


def build_spec_decode_loop(cfg: ModelConfig, mesh: Mesh, scfg: ServeConfig,
                           abstract_params: Any, abstract_draft: Any,
                           abstract_cache: Any, paged: bool = False,
                           view_pages: Optional[int] = None) -> Callable:
    """(params, draft_params, cache, state, key[, ptab])
    → (cache, state, tokens, emitted, drafted, accepted).

    The speculative twin of :func:`build_decode_loop`: each of the
    ``decode_chunk`` scan steps

      1. emits the carry token (sampled by the previous step / prefill),
      2. *drafts* ``spec_k`` tokens per slot with ``draft_params`` — an
         inner scan of single-token decode steps at the slot's own
         positions, exactly the sparse decode geometry (``M = slots``),
      3. runs ONE batched verify forward over the ``(slots, spec_k+1)``
         block with the dense ``params`` (``models.decode_block``,
         ``M = slots*(spec_k+1)``), which also re-writes the block's KV
         rows with verify-model values,
      4. accepts per slot the longest draft prefix the verify agrees
         with (greedy: token match; temperature: residual rejection
         sampling) and commits it — ``cache_pos`` advances by the
         emitted count, rejected rows are dead by masking, and the
         hybrid family's recurrent state is truncated to the accepted
         prefix via the per-position snapshots.

    The host block is ``(decode_chunk * (spec_k+1), slots)`` — still one
    device→host transfer per chunk, now also carrying the drafted /
    accepted totals for the acceptance-rate stats.  A slot freezes when
    fewer than ``spec_k + 1`` cache rows remain (the block write must
    stay in bounds), so full parity with the plain loop needs
    ``max_len ≥ prompt_rows + max_new + spec_k``.  Sampling runs at the
    uniform ``scfg.temperature`` (residual acceptance needs the draft
    and verify distributions at one temperature).
    """
    pspecs = SH.param_specs(abstract_params, cfg, mesh)
    dspecs = SH.param_specs(abstract_draft, cfg, mesh)
    cspecs = SH.cache_specs(abstract_cache, cfg, mesh, kv_mode=scfg.kv_mode)
    V = cfg.vocab_size
    K = scfg.spec_k
    T = scfg.temperature

    def spec_step(params, dparams, cache, st, skey):
        """One draft+verify+commit step; ``cache`` is the (possibly
        view-narrowed) cache the models run against."""
        tok, pos = st["tok"], st["pos"]
        done, left = st["done"], st["left"]
        # emit the carry token (same contract as the plain loop), but
        # freeze while the whole drafted block still fits below max_len
        emit0 = (~done) & (left > 0)
        left = left - emit0
        done = done | (emit0 & ((tok == scfg.eos_token) | (left == 0)
                                | (pos + 1 + K >= scfg.max_len)))
        alive = ~done

        rec0 = MZ.recurrent_state(cache)

        def draft_body(c, i):
            dcache, dtok = c
            lg, dcache = MZ.decode_step(dparams, cfg, dtok, dcache, pos + i)
            lg = lg[:, :V]
            nxt = sample_token_folded(lg, jax.random.fold_in(skey, i), T)
            return (dcache, nxt), (nxt, lg)

        (dcache, _), (drafts, dlogits) = jax.lax.scan(
            draft_body, (cache, tok), jnp.arange(K))
        # drafts (K, B): d_1..d_K; dlogits (K, B, V): the dists they came
        # from.  The draft advanced any recurrent state — restore it, the
        # verify block consumes d_0..d_K itself (KV rows are re-written
        # by the verify's own scatter, so they need no restore).
        dcache = MZ.set_recurrent_state(dcache, rec0)
        block = jnp.concatenate([tok[None], drafts], 0).T    # (B, K+1)
        vlg, cache, snaps = MZ.decode_block(
            params, cfg, block, dcache, pos,
            collect_states=rec0 is not None)
        vlg = vlg[:, :, :V]
        dT = drafts.T                                        # (B, K)

        if T <= 0.0:
            # greedy: accept drafts while they equal the verify argmax;
            # the first mismatch position supplies the correction token,
            # full acceptance supplies the bonus token — either way the
            # carry is g[j]
            g = jnp.argmax(vlg, axis=-1).astype(jnp.int32)   # (B, K+1)
            acc = jnp.cumprod((dT == g[:, :K]).astype(jnp.int32), axis=1)
            j = acc.sum(axis=1)                              # (B,)
            carry_tok = jnp.take_along_axis(g, j[:, None], 1)[:, 0]
        else:
            # residual (rejection) sampling — the lossless acceptance
            # rule: accept d_i with prob min(1, p_verify/p_draft); on
            # the first rejection resample from max(p_v - p_d, 0); on
            # full acceptance the residual degenerates to p_verify at
            # the bonus position.
            pv = jax.nn.softmax(vlg / T, axis=-1)            # (B, K+1, V)
            pd = jax.nn.softmax(dlogits / T, axis=-1)        # (K, B, V)
            pd = pd.transpose(1, 0, 2)                       # (B, K, V)
            pv_t = jnp.take_along_axis(pv[:, :K], dT[..., None],
                                       axis=-1)[..., 0]      # (B, K)
            pd_t = jnp.take_along_axis(pd, dT[..., None],
                                       axis=-1)[..., 0]
            u = jnp.stack([
                _slot_uniform(jax.random.fold_in(skey, K + 1 + i),
                              dT.shape[0]) for i in range(K)], axis=1)
            accept = u * pd_t <= pv_t                        # (B, K)
            acc = jnp.cumprod(accept.astype(jnp.int32), axis=1)
            j = acc.sum(axis=1)
            pv_j = jnp.take_along_axis(
                pv, j[:, None, None], axis=1)[:, 0]          # (B, V)
            pd_pad = jnp.concatenate(
                [pd, jnp.zeros_like(pd[:, :1])], axis=1)     # (B, K+1, V)
            pd_j = jnp.take_along_axis(
                pd_pad, j[:, None, None], axis=1)[:, 0]
            res = jnp.maximum(pv_j - pd_j, 0.0)
            res_sum = res.sum(-1, keepdims=True)
            res = jnp.where(res_sum > 0, res / res_sum, pv_j)
            res_logits = jnp.where(res > 0, jnp.log(res), -1e30)
            carry_tok = sample_token_folded(
                res_logits, jax.random.fold_in(skey, 2 * K + 2), 1.0)

        # commit-and-emit the accepted drafts: budget and EOS can cut
        # the accepted prefix short exactly like the plain loop would
        accb = acc.astype(bool)
        eos_hit = accb & (dT == scfg.eos_token)
        eos_before = (jnp.cumsum(eos_hit.astype(jnp.int32), axis=1)
                      - eos_hit.astype(jnp.int32)) > 0
        in_budget = jnp.arange(K)[None, :] < left[:, None]
        emit_d = alive[:, None] & accb & in_budget & ~eos_before
        n_emit = emit_d.sum(axis=1).astype(left.dtype)
        left = left - n_emit
        done = done | (alive & ((emit_d & eos_hit).any(axis=1)
                                | (left == 0)))
        pos = jnp.where(alive, pos + 1 + n_emit, pos)
        tok = jnp.where(~done, carry_tok, tok)

        if snaps is not None:
            # recurrent state can't roll back by masking: truncate it to
            # the accepted prefix (state after d_0..d_{n_emit}); frozen
            # slots keep their pre-block state
            sel = MZ.select_recurrent(snaps, n_emit.astype(jnp.int32))
            cache = MZ.set_recurrent_state(
                cache, MZ.where_slot(alive, sel, rec0))

        st = {"tok": tok, "pos": pos, "done": done, "left": left}
        # column 0 is the carry token (block[:, 0]), columns 1..K the
        # drafted candidates — the emit mask says which ones landed
        step_tokens = jnp.concatenate([block[:, :1], dT], axis=1)
        step_emits = jnp.concatenate([emit0[:, None], emit_d], axis=1)
        drafted = jnp.where(alive, K, 0).sum()
        accepted = jnp.where(alive, j, 0).sum()
        return cache, st, step_tokens, step_emits, drafted, accepted

    def scan_chunk(params, dparams, cache, state, key):
        def body(carry, step):
            cache, st, key = carry
            skey = jax.random.fold_in(key, step)
            if paged:
                vcache = MZ.page_view(cache, view_pages)
                vcache, st, toks, emits, dr, ac = spec_step(
                    params, dparams, vcache, st, skey)
                cache = MZ.unpage_view(vcache, cache)
            else:
                cache, st, toks, emits, dr, ac = spec_step(
                    params, dparams, cache, st, skey)
            return (cache, st, key), (toks, emits, dr, ac)

        (cache, state, _), (toks, emits, dr, ac) = jax.lax.scan(
            body, (cache, state, key), jnp.arange(scfg.decode_chunk))
        # (steps, B, K+1) → time-major (steps*(K+1), B): the same block
        # layout the plain loop hands the host, just taller
        tokens = toks.transpose(0, 2, 1).reshape(-1, toks.shape[1])
        emitted = emits.transpose(0, 2, 1).reshape(-1, emits.shape[1])
        return cache, state, tokens, emitted, dr.sum(), ac.sum()

    sspecs = _state_shardings(mesh)
    if paged:
        def loop(params, dparams, cache, state, key, ptab):
            cache = MZ.set_page_table(cache, ptab)
            return scan_chunk(params, dparams, cache, state, key)

        # token block + drafted/accepted tallies replicate (see
        # build_decode_loop): the chunk fetch never gathers cross-shard
        rep = NamedSharding(mesh, P())
        return jax.jit(
            loop,
            in_shardings=(SH.named(mesh, pspecs), SH.named(mesh, dspecs),
                          SH.named(mesh, cspecs), sspecs, None, None),
            out_shardings=(SH.named(mesh, cspecs), sspecs, rep, rep,
                           rep, rep),
            donate_argnums=(2, 3))

    rep = NamedSharding(mesh, P())
    return jax.jit(
        scan_chunk,
        in_shardings=(SH.named(mesh, pspecs), SH.named(mesh, dspecs),
                      SH.named(mesh, cspecs), sspecs, None),
        out_shardings=(SH.named(mesh, cspecs), sspecs, rep, rep,
                       rep, rep),
        donate_argnums=(2, 3))
