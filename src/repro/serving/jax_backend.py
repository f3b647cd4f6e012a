"""JAX paged-KV backend for ReplicaCore: real prefill / decode / sampling
over the shared page pool via `model_runner`, while every scheduling
decision (admission, eviction, preemption, chunking) stays in
`repro.replica.core.ReplicaCore`.

The hot path is shape-stable and single-dispatch-per-step:

  decode   The batch lives in a PERSISTENT DEVICE-RESIDENT state (block
           tables, seq lens, last sampled tokens, per-row sampling params)
           at full capacity shape; `mr.decode_step` slices the active
           power-of-two bucket `(nb, npgb)` inside the jit, so steady-state
           steps upload NOTHING and compile from a bounded bucket set. The
           fused step advances lens/tokens on device — sampled tokens feed
           the next step's embedding straight from the device buffer; the
           host only downloads them once per step for scheduler
           bookkeeping. Host mirrors are updated incrementally and the
           device state is re-uploaded only when batch MEMBERSHIP changes
           (admission / completion / preemption), detected by sequence and
           block-table identity.

  prefill  Admissions are packed: `prefill_batch` ragged-packs every
           admitted suffix into ONE `mr.prefill_pack_step` dispatch
           (per-token segment ids / positions / page destinations), with
           each segment attending to its own radix-cached prefix and its
           boundary token sampled on device. The one-request
           `mr.prefill_step` path remains as the `packed_prefill=False`
           fallback.

Sampling is per-sequence (each row's temperature/top-k ride in device
arrays) and batch-shape-invariant and run-stable (PRNG keyed on the request's
sampling seed + token position),
so bucketing can never change sampled tokens.

Placement follows the params: the KV pools, the batch state, the PRNG key
and every upload live on the one device that holds `params`, so engines
whose params sit on different chips serve from those chips.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.serving import model_runner as mr
from repro.serving.bucketing import bucket, pow2_pad, token_pad
from repro.serving.spans import span


@jax.jit
def _gather_pages(k_pages, v_pages, ids):
    return ops.page_gather(k_pages, v_pages, ids)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_pages(k_pages, v_pages, k_stack, v_stack, ids):
    # donated: the import updates the pools in place instead of
    # allocating a second pool beside the first
    return ops.page_scatter(k_pages, v_pages, k_stack, v_stack, ids)


class JaxPagedBackend:
    """ReplicaBackend over a real paged KV pool. Must be `bind()`-ed to its
    ReplicaCore after construction: the core's reserved pages provide the
    scratch page ids used to pad block tables (never read back thanks to
    seq_len masking, but they must stay allocated), and the core's config
    sizes the persistent device batch state."""

    def __init__(self, model_cfg: ModelConfig, params: Any, *,
                 n_pages: int, page_size: int, prefill_pad: int = 64,
                 seed: int = 0, bucket_shapes: bool = True,
                 packed_prefill: bool = True, overlap_loads: bool = True,
                 spec_k: int = 0, draft_cfg: Optional[ModelConfig] = None,
                 draft_params: Any = None,
                 spec_synth_rate: Optional[float] = None):
        self.cfg = model_cfg
        self.params = params
        self.page_size = page_size
        self.prefill_pad = prefill_pad
        self.bucket_shapes = bucket_shapes
        self.packed_prefill = packed_prefill
        self.overlap_loads = overlap_loads
        leaf = jax.tree.leaves(params)[0]
        kv_dtype = leaf.dtype
        devices = leaf.devices()
        if len(devices) != 1:
            raise ValueError(f"JaxPagedBackend serves params held on one "
                             f"device; these span {len(devices)}")
        (self.device,) = devices
        self.k_pages, self.v_pages = mr.init_kv_pool(
            model_cfg, n_pages, page_size, kv_dtype, device=self.device)
        # speculative decoding: the drafter keeps its OWN pools with the
        # target pool's page geometry (same page ids index both), so the
        # scheduler manages one set of pages for two models
        self.spec_k = spec_k
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params
        self.spec_synth_rate = spec_synth_rate
        if spec_k > 0:
            if draft_cfg is None or draft_params is None:
                raise ValueError("spec_k > 0 requires draft_cfg + "
                                 "draft_params (the drafter model)")
            self.dk_pages, self.dv_pages = mr.init_kv_pool(
                draft_cfg, n_pages, page_size, kv_dtype, device=self.device)
        else:
            self.dk_pages = self.dv_pages = None
        self.spec_dispatches = 0      # decode_many calls
        self.spec_drafted = 0         # draft positions proposed
        self.spec_accepted = 0        # draft positions accepted
        self._base_key = self._put(jax.random.PRNGKey(seed))
        self._scratch: Optional[int] = None
        # host KV tier (allocated at bind when the core enables it)
        self._h_k: Optional[np.ndarray] = None
        self._h_v: Optional[np.ndarray] = None
        self._demote_q: list[tuple[int, int]] = []   # (dev_page, host_page)
        self._staging: dict = {}                     # seq -> staged H2D copy
        self.demoted_pages = 0
        self.loaded_pages = 0
        self.decode_calls = 0         # decode / decode_many dispatches
        self.state_uploads = 0        # _sync_slots calls (membership changed)

    def _put(self, x):
        """Upload a host value (or a pytree of them, in one call) to the
        params' device."""
        return jax.device_put(x, self.device)

    def bind(self, core) -> None:
        if not core.reserved:
            raise ValueError("JaxPagedBackend needs ReplicaCoreConfig."
                             "reserved_pages >= 1 for block-table padding")
        self._scratch = core.reserved[0]
        ccfg = core.cfg
        pool = ccfg.n_pages - ccfg.reserved_pages
        self._bcap = ccfg.max_batch or max(1, pool)
        max_len = ccfg.max_seq_len or pool * self.page_size
        self._npg_cap = max(1, -(-max_len // self.page_size))
        # host mirrors of the device batch state (updated incrementally;
        # uploaded only when membership changes)
        self._m_bt = np.full((self._bcap, self._npg_cap), self._scratch,
                             np.int32)
        self._m_lens = np.zeros(self._bcap, np.int32)
        self._m_toks = np.zeros(self._bcap, np.int32)
        self._m_temps = np.zeros(self._bcap, np.float32)
        self._m_topks = np.zeros(self._bcap, np.int32)
        self._m_seeds = np.zeros(self._bcap, np.int32)
        # (seq, its pages-list identity) per device row; a preempted+resumed
        # sequence gets a fresh pages list, so identity detects stale rows
        # even when it lands back on the same row
        self._slots: list = []
        self._dstate: Optional[dict] = None
        self._nb = 0
        self._npgb = 0
        if ccfg.host_pages:
            shp = (ccfg.host_pages,) + self.k_pages.shape[:1] \
                + self.k_pages.shape[2:]             # (H, L, page, K, hd)
            self._h_k = np.zeros(shp, self.k_pages.dtype)
            self._h_v = np.zeros(shp, self.k_pages.dtype)

    # --------------------------------------------------------- host tier
    def on_demote(self, dev_page: int, host_page: int) -> None:
        """Radix demotion hook: queue the D2H snapshot. The gather runs
        lazily at the next dispatch boundary — the pool still holds the
        page's KV then, because freed pages are only REWRITTEN by a later
        prefill/scatter dispatch, and every such dispatch flushes first."""
        self._demote_q.append((dev_page, host_page))

    def _flush_demotes(self) -> None:
        if not self._demote_q:
            return
        q, self._demote_q = self._demote_q, []
        n = len(q)
        pad = self._pow2_pad(n)
        ids = np.fromiter((d for d, _ in q), np.int32, n)
        ids = np.concatenate([ids, np.zeros(pad - n, np.int32)])
        ks, vs = _gather_pages(self.k_pages, self.v_pages, self._put(ids))
        kh, vh = np.asarray(ks), np.asarray(vs)      # one sync per flush
        for i, (_, hp) in enumerate(q):
            self._h_k[hp] = kh[i]
            self._h_v[hp] = vh[i]
        self.demoted_pages += n

    def load_pages(self, seq, pairs) -> None:
        """Dispatch the host->device copy for a LOADING admission: the
        staged stacks start their H2D transfer NOW (jax.device_put is
        async) and land in the pool at `finish_load` — the transfer
        overlaps this step's decode. Per-seq staging entries double-buffer
        concurrent loads."""
        self._flush_demotes()
        dev_ids = [dp for _, dp in pairs]
        k_stack = np.stack([self._h_k[hp] for hp, _ in pairs])
        v_stack = np.stack([self._h_v[hp] for hp, _ in pairs])
        k_dev, v_dev = self._put((k_stack, v_stack))
        if not self.overlap_loads:                   # serialize (benchmarks)
            jax.block_until_ready((k_dev, v_dev))
        self._staging[seq] = (dev_ids, k_dev, v_dev)

    def finish_load(self, seq) -> None:
        self._flush_demotes()
        dev_ids, k_dev, v_dev = self._staging.pop(seq)
        n = len(dev_ids)
        pad = self._pow2_pad(n)
        # pad with the scratch page: its contents are never read back
        ids = np.asarray(dev_ids + [self._scratch] * (pad - n), np.int32)
        if pad > n:
            reps = np.zeros(pad, np.int32)
            reps[:n] = np.arange(n)
            k_dev, v_dev = k_dev[reps], v_dev[reps]
        self.k_pages, self.v_pages = _scatter_pages(
            self.k_pages, self.v_pages, k_dev, v_dev, self._put(ids))
        self.loaded_pages += n

    def abort_load(self, seq) -> None:
        self._staging.pop(seq, None)

    # ------------------------------------------- cross-engine KV transfer
    def export_pages(self, pages: list) -> tuple:
        """Pull the KV of `pages` (device page ids) into host numpy stacks
        (N, L, page, K, hd) — the wire format of cross-region pull-prefix."""
        self._flush_demotes()
        n = len(pages)
        pad = self._pow2_pad(n)
        ids = np.asarray(list(pages) + [0] * (pad - n), np.int32)
        ks, vs = _gather_pages(self.k_pages, self.v_pages, self._put(ids))
        return np.asarray(ks)[:n], np.asarray(vs)[:n]

    def import_pages(self, pages: list, k_stack, v_stack) -> None:
        """Write transferred KV stacks into local device `pages`."""
        self._flush_demotes()
        n = len(pages)
        pad = self._pow2_pad(n)
        ids = np.asarray(list(pages) + [self._scratch] * (pad - n), np.int32)
        if pad > n:
            reps = np.zeros(pad, np.int32)
            reps[:n] = np.arange(n)
            k_stack, v_stack = k_stack[reps], v_stack[reps]
        self.k_pages, self.v_pages = _scatter_pages(
            self.k_pages, self.v_pages, *self._put((k_stack, v_stack, ids)))

    # ------------------------------------------------------------ prefill
    def _sample_pref(self, logits, seq, pos: int):
        """Sample one prefill boundary token (same per-row RNG as the
        packed/decode paths, so every path draws identical tokens)."""
        sp = seq.req.sampling
        tok = mr.sample_rows(logits, self._base_key, *self._put((
            np.asarray([sp.seed], np.int32),
            np.asarray([pos], np.int32),
            np.asarray([sp.temperature], np.float32),
            np.asarray([sp.top_k], np.int32))))
        with span("backend.sync"):
            return int(np.asarray(tok)[0])

    def prefill(self, seq, start: int, end: int, sample: bool) -> Optional[int]:
        """One-request fallback (`packed_prefill=False`); the packed path
        below is the default."""
        self._flush_demotes()
        ps = self.page_size
        suffix = seq.tokens[start:end]
        S = self._token_pad(len(suffix))
        toks = np.zeros((1, S), np.int32)
        toks[0, :len(suffix)] = suffix
        # page list covering all S (padded) rows: this chunk's pages first,
        # then the scratch page repeated (padding rows write garbage there;
        # rows past len(suffix) inside real pages are masked until decode
        # overwrites them)
        np_total = -(-S // ps)
        chunk_pages = seq.pages[start // ps: -(-end // ps)]
        np_new = np.asarray(
            (chunk_pages + [self._scratch] * np_total)[:max(np_total, 1)],
            np.int32)
        past = seq.pages[:start // ps]
        np_past = np.asarray(past if past else [self._scratch], np.int32)
        toks, np_new = self._put((toks, np_new))
        rest = self._put((np_past, np.int32(start), np.int32(len(suffix))))
        logits, self.k_pages, self.v_pages = mr.prefill_step(
            self.params, toks, np_new, self.k_pages, self.v_pages, *rest,
            cfg=self.cfg, page_size=ps)
        if self.spec_k > 0:
            # mirror the chunk through the drafter so its cache tracks the
            # target's committed positions (same pages, its own pools)
            _, self.dk_pages, self.dv_pages = mr.prefill_step(
                self.draft_params, toks, np_new, self.dk_pages,
                self.dv_pages, *rest, cfg=self.draft_cfg, page_size=ps)
        if not sample:
            return None
        tok = self._sample_pref(logits, seq, end)
        if seq.req.first_token_s is None:
            seq.req.first_token_s = time.monotonic()
        return tok

    def prefill_batch(self, items) -> list:
        """Packed batched prefill: one dispatch for a whole admission round.
        items: [(seq, start, end, sample)] with page-aligned starts."""
        with span("backend.prefill"):
            if not self.packed_prefill:
                return [self.prefill(seq, s, e, smp)
                        for seq, s, e, smp in items]
            self._flush_demotes()
            ps = self.page_size
            nseg = len(items)
            seg_lens = [end - start for _, start, end, _ in items]
            S = self._token_pad(sum(seg_lens))
            toks = np.zeros(S, np.int32)
            segs = np.full(S, -1, np.int32)
            poss = np.zeros(S, np.int32)
            dpage = np.full(S, self._scratch, np.int32)
            dslot = np.zeros(S, np.int32)
            past_lists = []
            off = 0
            for j, (seq, start, end, _) in enumerate(items):
                n = end - start
                idx = np.arange(start, end)
                toks[off:off + n] = seq.tokens[start:end]
                segs[off:off + n] = j
                poss[off:off + n] = idx
                dpage[off:off + n] = np.asarray(seq.pages, np.int32)[idx // ps]
                dslot[off:off + n] = idx % ps
                past_lists.append(seq.pages[:start // ps])
                off += n
            cp_off = np.cumsum([0] + [len(p) for p in past_lists])
            CP = self._pow2_pad(max(int(cp_off[-1]), 1))
            past = np.full(CP, self._scratch, np.int32)
            for j, pages in enumerate(past_lists):
                past[cp_off[j]:cp_off[j + 1]] = pages
            NS = self._pow2_pad(nseg)
            past_start = np.zeros(NS, np.int32)
            past_len = np.zeros(NS, np.int32)
            last_idx = np.zeros(NS, np.int32)
            temps = np.zeros(NS, np.float32)
            topks = np.zeros(NS, np.int32)
            seeds = np.zeros(NS, np.int32)
            spos = np.zeros(NS, np.int32)
            seg_off = np.cumsum([0] + seg_lens)
            for j, (seq, start, end, _) in enumerate(items):
                sp = seq.req.sampling
                past_start[j] = cp_off[j] * ps
                past_len[j] = start
                last_idx[j] = seg_off[j + 1] - 1
                temps[j] = sp.temperature
                topks[j] = sp.top_k
                seeds[j] = sp.seed
                spos[j] = end
            tok_rows = self._put((toks, segs, poss, dpage, dslot))
            seg_rows = self._put((past, past_start, past_len, last_idx, temps,
                                  topks, seeds, spos))
            toks_dev, self.k_pages, self.v_pages = mr.prefill_pack_step(
                self.params, *tok_rows, self.k_pages, self.v_pages, *seg_rows,
                self._base_key, cfg=self.cfg, page_size=ps)
            if self.spec_k > 0:
                # drafter mirror of the whole packed round (sampled boundary
                # tokens are the target's business; the drafter only needs its
                # cache to hold every committed position)
                _, self.dk_pages, self.dv_pages = mr.prefill_pack_step(
                    self.draft_params, *tok_rows, self.dk_pages, self.dv_pages,
                    *seg_rows, self._base_key, cfg=self.draft_cfg,
                    page_size=ps)
            with span("backend.sync"):
                tn = np.asarray(toks_dev)          # one host sync per round
            now = time.monotonic()
            out: list = []
            for j, (seq, _start, _end, smp) in enumerate(items):
                if not smp:
                    out.append(None)
                    continue
                if seq.req.first_token_s is None:
                    seq.req.first_token_s = now
                out.append(int(tn[j]))
            return out

    # ------------------------------------------------------------ decode
    def decode(self, seqs) -> list[int]:
        with span("backend.decode"):
            self._flush_demotes()
            n = len(seqs)
            self.decode_calls += 1
            if not self._slots_current(seqs):
                self._sync_slots(seqs)
            toks, self._dstate, self.k_pages, self.v_pages = mr.decode_step(
                self.params, self._dstate, self.k_pages, self.v_pages,
                self._base_key, cfg=self.cfg, page_size=self.page_size,
                nb=self._nb, npgb=self._npgb)
            with span("backend.sync"):
                out = np.asarray(toks)             # the single host sync
            # advance the mirrors exactly like the fused step advanced the
            # device state (active rows only)
            active = self._m_lens[:self._nb] > 0
            self._m_lens[:self._nb] += active
            self._m_toks[:self._nb] = np.where(active, out[:self._nb],
                                               self._m_toks[:self._nb])
            return [int(t) for t in out[:n]]

    def decode_many(self, seqs) -> Optional[list]:
        """ReplicaCore's speculative step contract: None when speculation
        is off (core falls back to `decode`); else ONE fused
        `mr.spec_decode_step` dispatch over the same persistent bucketed
        batch state, and — like `decode` — a single host sync per step.
        Returns the n_acc+1 verified tokens per sequence, all of them
        target samples (bit-identical to the sequential engine unless the
        synthetic-acceptance bench knob is set). The drafter's pools are
        NOT moved by the host tier or cross-region import, so a reloaded
        prefix degrades acceptance, never correctness."""
        if self.spec_k <= 0:
            return None
        with span("backend.decode"):
            self._flush_demotes()
            n = len(seqs)
            self.decode_calls += 1
            if not self._slots_current(seqs):
                self._sync_slots(seqs)
            (T, n_acc, self._dstate, self.k_pages, self.v_pages,
             self.dk_pages, self.dv_pages) = mr.spec_decode_step(
                self.params, self.draft_params, self._dstate,
                self.k_pages, self.v_pages, self.dk_pages, self.dv_pages,
                self._base_key, self._put(np.int32(self._scratch)),
                cfg=self.cfg, dcfg=self.draft_cfg, page_size=self.page_size,
                nb=self._nb, npgb=self._npgb, k_spec=self.spec_k,
                synth_rate=self.spec_synth_rate)
            with span("backend.sync"):
                Tn, an = jax.device_get((T, n_acc))    # the single host sync
            # advance the mirrors exactly like the fused step advanced the
            # device state (active rows move past their accepted run + 1)
            active = self._m_lens[:self._nb] > 0
            self._m_lens[:self._nb] += np.where(active, an + 1,
                                                0).astype(np.int32)
            rows = np.arange(self._nb)
            self._m_toks[:self._nb] = np.where(active, Tn[rows, an],
                                               self._m_toks[:self._nb])
            self.spec_dispatches += 1
            self.spec_drafted += n * self.spec_k
            self.spec_accepted += int(an[:n].sum())
            return [[int(t) for t in Tn[i, :an[i] + 1]] for i in range(n)]

    def _slots_current(self, seqs) -> bool:
        if len(self._slots) != len(seqs):
            return False
        return all(sl_seq is s and sl_pages is s.pages
                   for (sl_seq, sl_pages), s in zip(self._slots, seqs))

    def _sync_slots(self, seqs) -> None:
        """Batch membership changed: rewrite the rows that differ, zero the
        rows that emptied, pick the shape bucket, upload the state."""
        self.state_uploads += 1
        with span("backend.upload"):
            n = len(seqs)
            old = self._slots
            for i, s in enumerate(seqs):
                if i < len(old) and old[i][0] is s and old[i][1] is s.pages:
                    continue
                self._m_bt[i, :] = self._scratch
                self._m_bt[i, :len(s.pages)] = s.pages
                self._m_lens[i] = s.pos - 1    # last token not yet in cache
                self._m_toks[i] = s.tokens[-1]
                sp = s.req.sampling
                self._m_temps[i] = sp.temperature
                self._m_topks[i] = sp.top_k
                self._m_seeds[i] = sp.seed
            for i in range(n, len(old)):           # rows that shrank away
                self._m_bt[i, :] = self._scratch
                self._m_lens[i] = 0
                self._m_toks[i] = 0
                self._m_temps[i] = 0.0
                self._m_topks[i] = 0
                self._m_seeds[i] = 0
            self._slots = [(s, s.pages) for s in seqs]
            npg_need = max(len(s.pages) for s in seqs)
            if self.bucket_shapes:
                self._nb = bucket(n, self._bcap)
                self._npgb = bucket(npg_need, self._npg_cap)
            else:
                self._nb, self._npgb = n, npg_need
            self._dstate = self._put({
                "bt": self._m_bt,
                "lens": self._m_lens,
                "toks": self._m_toks,
                "temps": self._m_temps,
                "top_ks": self._m_topks,
                "seeds": self._m_seeds,
            })

    # ------------------------------------------------------------ shapes
    # (one implementation for every caller: repro.serving.bucketing)
    def _token_pad(self, n: int) -> int:
        return token_pad(n, self.prefill_pad, self.bucket_shapes)

    def _pow2_pad(self, n: int) -> int:
        return pow2_pad(n, self.bucket_shapes)
