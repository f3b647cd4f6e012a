"""Jitted dispatch wrappers: Pallas kernels on TPU, pure-jnp oracles
(ref.py) elsewhere. Import this module, not the kernels, from model code.

Set REPRO_FORCE_INTERPRET=1 to run the Pallas kernel bodies in interpret
mode on CPU (used by the kernel test sweeps — validates the kernels
themselves, not just the oracles). A backend that fails to start raises
here; it never turns into a silent oracle run.
"""
from __future__ import annotations

import os

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.page_copy import page_gather as _gather_pallas
from repro.kernels.page_copy import page_scatter as _scatter_pallas
from repro.kernels.paged_decode import paged_decode as _paged_pallas
from repro.kernels.paged_verify import paged_verify as _verify_pallas
from repro.kernels.ssd_scan import ssd_scan as _ssd_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _force_interpret() -> bool:
    return os.environ.get("REPRO_FORCE_INTERPRET", "") == "1"


def flash_attention(q, k, v, *, causal: bool = True):
    """q: (B,H,S,hd); k/v: (B,K,T,hd). Pallas on TPU, oracle on CPU."""
    if _on_tpu():
        return _flash_pallas(q, k, v, causal=causal)
    if _force_interpret():
        return _flash_pallas(q, k, v, causal=causal, interpret=True)
    return ref.flash_attention_ref(q, k, v, causal=causal)


def paged_decode(q, k_pages, v_pages, block_table, seq_lens):
    """q: (B,H,hd); pools (P,page,K,hd); block_table (B,NPG); seq_lens (B,)."""
    if _on_tpu():
        return _paged_pallas(q, k_pages, v_pages, block_table, seq_lens)
    if _force_interpret():
        return _paged_pallas(q, k_pages, v_pages, block_table, seq_lens,
                             interpret=True)
    return ref.paged_decode_ref(q, k_pages, v_pages, block_table, seq_lens)


def paged_verify(q, k_pages, v_pages, block_table, seq_lens):
    """q: (B,Q,H,hd) — Q speculative candidates per sequence; pools
    (P,page,K,hd); block_table (B,NPG); seq_lens (B,) TOTAL valid tokens
    including the Q candidates (>= Q)."""
    if _on_tpu():
        return _verify_pallas(q, k_pages, v_pages, block_table, seq_lens)
    if _force_interpret():
        return _verify_pallas(q, k_pages, v_pages, block_table, seq_lens,
                              interpret=True)
    return ref.paged_verify_ref(q, k_pages, v_pages, block_table, seq_lens)


def page_gather(k_pages, v_pages, ids):
    """Pull pages `ids` out of the (L,P,page,K,hd) pools into dense
    (N,L,page,K,hd) stacks (the demotion D2H staging layout)."""
    if _on_tpu():
        return _gather_pallas(k_pages, v_pages, ids)
    if _force_interpret():
        return _gather_pallas(k_pages, v_pages, ids, interpret=True)
    return (ref.page_gather_ref(k_pages, ids),
            ref.page_gather_ref(v_pages, ids))


def page_scatter(k_pages, v_pages, k_stack, v_stack, ids):
    """Write staged stacks back into the pools at page slots `ids`,
    in place (aliased) on TPU."""
    if _on_tpu():
        return _scatter_pallas(k_pages, v_pages, k_stack, v_stack, ids)
    if _force_interpret():
        return _scatter_pallas(k_pages, v_pages, k_stack, v_stack, ids,
                               interpret=True)
    return (ref.page_scatter_ref(k_pages, k_stack, ids),
            ref.page_scatter_ref(v_pages, v_stack, ids))


def ssd_scan(x, dt, a, B_, C_, *, chunk: int = 128):
    """Chunked SSD; see kernels.ssd_scan. Pallas on TPU, oracle on CPU."""
    if _on_tpu():
        return _ssd_pallas(x, dt, a, B_, C_, chunk=chunk)
    if _force_interpret():
        return _ssd_pallas(x, dt, a, B_, C_, chunk=chunk, interpret=True)
    return ref.ssd_scan_ref(x, dt, a, B_, C_, chunk=chunk)
