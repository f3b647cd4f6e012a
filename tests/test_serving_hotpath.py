"""Shape-stable serving hot path: bucketed-vs-exact decode parity, packed-
vs-sequential prefill parity, per-sequence (mixed) sampling, and the
compile-count regression that guards the recompile-free property."""
from __future__ import annotations

import numpy as np
import pytest

from repro.serving import Engine, EngineConfig, GenRequest, SamplingParams
from repro.serving import model_runner as mr
from repro.serving.bucketing import bucket, bucket_tokens, n_buckets, next_pow2


def _reqs(vocab, specs, seed=0):
    """specs: [(prompt_len, sampling kwargs)] -> deterministic requests."""
    rng = np.random.default_rng(seed)
    return [GenRequest(
        prompt_tokens=tuple(rng.integers(0, vocab, size=n).tolist()),
        sampling=SamplingParams(**kw)) for n, kw in specs]


MIXED = [(12, dict(max_new_tokens=6)),
         (23, dict(max_new_tokens=5, temperature=0.7, top_k=3, seed=1)),
         (9, dict(max_new_tokens=7, temperature=1.1)),
         (31, dict(max_new_tokens=4)),
         (17, dict(max_new_tokens=6, temperature=0.4, top_k=8))]


def _run(qwen_reduced, qwen_model_params, specs, **ecfg_kw):
    _, params = qwen_model_params
    kw = dict(page_size=8, n_pages=64, max_batch=4, max_seq_len=256,
              prefill_pad=16)
    kw.update(ecfg_kw)
    eng = Engine(qwen_reduced, params, EngineConfig(**kw), seed=0)
    res = eng.generate(_reqs(qwen_reduced.vocab, specs))
    return [r.output_tokens for r in res]


# ----------------------------------------------------------------- parity

def test_bucketed_vs_exact_decode_parity(qwen_reduced, qwen_model_params):
    """Pow2 shape buckets must not change a single sampled token: the
    padded rows/pages are masked and the per-row RNG is keyed on
    (rid, position), never on batch shape."""
    a = _run(qwen_reduced, qwen_model_params, MIXED, bucket_shapes=True)
    b = _run(qwen_reduced, qwen_model_params, MIXED, bucket_shapes=False)
    assert a == b


def test_packed_vs_sequential_prefill_parity(qwen_reduced, qwen_model_params):
    """Packing admissions into one prefill dispatch must sample the same
    boundary tokens as one-request-at-a-time prefill."""
    a = _run(qwen_reduced, qwen_model_params, MIXED, packed_prefill=True)
    b = _run(qwen_reduced, qwen_model_params, MIXED, packed_prefill=False)
    assert a == b


def test_packed_prefill_parity_with_chunking(qwen_reduced, qwen_model_params):
    """Chunked prefill rounds (one chunk per sequence per round) keep the
    same semantics as sequential chunked prefill."""
    specs = [(40, dict(max_new_tokens=4)),
             (25, dict(max_new_tokens=4, temperature=0.8, top_k=5)),
             (33, dict(max_new_tokens=3))]
    a = _run(qwen_reduced, qwen_model_params, specs,
             packed_prefill=True, prefill_chunk=16)
    b = _run(qwen_reduced, qwen_model_params, specs,
             packed_prefill=False, prefill_chunk=16)
    c = _run(qwen_reduced, qwen_model_params, specs, packed_prefill=True)
    assert a == b == c


# --------------------------------------------------------- mixed sampling

def test_mixed_sampling_per_sequence(qwen_reduced, qwen_model_params):
    """Regression for the whole-batch `seqs[0].req.sampling` bug: each
    sequence must be sampled with ITS OWN temperature/top-k. A greedy
    request decoded alongside hot-temperature ones must produce exactly
    the tokens it produces alone."""
    greedy = (20, dict(max_new_tokens=6))
    hot = (15, dict(max_new_tokens=6, temperature=5.0, seed=3))
    solo = _run(qwen_reduced, qwen_model_params, [greedy])
    both = _run(qwen_reduced, qwen_model_params, [greedy, hot])
    assert both[0] == solo[0]
    # and the hot request really is stochastic (not greedy-sampled): at
    # temperature 5 on random logits a 6-token greedy match is ~impossible
    greedy_alone = _run(qwen_reduced, qwen_model_params,
                        [(15, dict(max_new_tokens=6))])
    assert both[1] != greedy_alone[0]


def test_sampling_deterministic_across_runs(qwen_reduced, qwen_model_params):
    a = _run(qwen_reduced, qwen_model_params, MIXED)
    b = _run(qwen_reduced, qwen_model_params, MIXED)
    assert a == b


def test_sample_fallback_matches_configs():
    """The standalone `sample` no longer treats temperature/top_k as
    static: distinct configs reuse ONE compiled program, and greedy still
    argmaxes."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    key = jax.random.PRNGKey(0)
    base = mr.sample._cache_size()
    greedy = mr.sample(logits, key, temperature=0.0, top_k=0)
    assert (np.asarray(greedy) == np.asarray(jnp.argmax(logits, -1))).all()
    for t, k in ((0.5, 0), (0.9, 5), (1.3, 1), (0.7, 31)):
        out = np.asarray(mr.sample(logits, key, temperature=t, top_k=k))
        assert out.shape == (4,) and (out >= 0).all() and (out < 32).all()
    assert mr.sample._cache_size() - base <= 1
    # top_k=1 == greedy regardless of temperature
    one = np.asarray(mr.sample(logits, key, temperature=2.0, top_k=1))
    assert (one == np.asarray(greedy)).all()


@pytest.mark.parametrize("case", ["normal", "ties", "signs"])
def test_kth_largest_matches_descending_sort(case):
    """The top-k threshold is the exact value a descending sort puts at
    index k-1 — ties, negatives, signed zeros and -inf included — so the
    mask it feeds is the sort-based mask, token for token."""
    import jax
    from repro.serving.sampling import kth_largest
    rng = np.random.default_rng(7)
    B, V = 6, 257
    x = rng.normal(scale=4.0, size=(B, V)).astype(np.float32)
    if case == "ties":
        x = np.round(x).astype(np.float32)            # heavy ties
    elif case == "signs":
        x[:, :40] = -np.abs(x[:, :40])
        x[:, 40:50] = -0.0
        x[:, 50:60] = 0.0
        x[:, 60:64] = -np.inf
    ks = np.array([1, 2, 17, 128, V - 1, V], np.int32)
    got = np.asarray(jax.jit(kth_largest)(x, ks))
    want = np.sort(x, axis=-1)[:, ::-1][np.arange(B), ks - 1]
    assert (got == want).all(), (got, want)


# ----------------------------------------------------------- compile churn

def test_decode_compile_count_bounded(qwen_reduced, qwen_model_params):
    """A varied-length workload through the bucketed engine must keep the
    decode_step jit cache bounded by the bucket-pair count — the
    recompile-free property the tentpole is about. Runs with per-token
    STREAMING enabled on every request: emitting TokenEvents must not add
    compile keys (or device dispatches) to the hot path."""
    _, params = qwen_model_params
    ecfg = EngineConfig(page_size=8, n_pages=64, max_batch=4,
                        max_seq_len=256, prefill_pad=16)
    eng = Engine(qwen_reduced, params, ecfg, seed=0)
    before = mr.compile_counts()["decode_step"]
    rng = np.random.default_rng(9)
    specs = [(int(n), dict(max_new_tokens=int(m)))
             for n, m in zip(rng.integers(5, 60, size=10),
                             rng.integers(3, 12, size=10))]
    reqs = _reqs(qwen_reduced.vocab, specs, seed=9)
    streamed = []
    for r in reqs:
        r.on_token = lambda req, tok, idx, t: streamed.append((req.rid, tok))
    res = eng.generate(reqs)
    grew = mr.compile_counts()["decode_step"] - before
    bound = n_buckets(ecfg.max_batch) * n_buckets(
        -(-ecfg.max_seq_len // ecfg.page_size))
    assert 0 < grew <= bound
    # the stream delivered every token exactly once
    assert len(streamed) == sum(len(r.output_tokens) for r in res)


def test_steady_state_uploads_nothing(qwen_reduced, qwen_model_params):
    """While batch membership is stable, decode must reuse the persistent
    device state: no _sync_slots re-upload between steps — with per-token
    streaming enabled (the event drain rides the step's existing single
    host sync; zero extra uploads or dispatches)."""
    _, params = qwen_model_params
    eng = Engine(qwen_reduced, params,
                 EngineConfig(page_size=8, n_pages=64, max_batch=4,
                              max_seq_len=256, prefill_pad=16))
    events = []
    for r in _reqs(qwen_reduced.vocab, [(10, dict(max_new_tokens=20)),
                                        (14, dict(max_new_tokens=20))]):
        r.on_token = lambda req, tok, idx, t: events.append((req.rid, idx))
        eng.submit(r)
    eng.step()                                  # admits both (prefill only)
    eng.step()                                  # first decode -> sync
    syncs = {"n": 0}
    orig = eng.backend._sync_slots

    def counting(seqs):
        syncs["n"] += 1
        return orig(seqs)

    eng.backend._sync_slots = counting
    uploads = eng.backend.state_uploads
    for _ in range(10):
        eng.step()
    assert syncs["n"] == 0                      # membership never changed
    assert eng.backend.state_uploads == uploads
    eng.run_until_idle()
    assert eng.completions == 2
    # streaming delivered all 40 tokens, in order, while uploading nothing
    assert len(events) == 40
    for rid in set(r for r, _ in events):
        assert [i for r, i in events if r == rid] == list(range(20))
    # a new member: one upload, at its first decode, and none after it
    eng.backend._sync_slots = orig
    eng.submit(_reqs(qwen_reduced.vocab, [(12, dict(max_new_tokens=5))])[0])
    eng.step()                                  # prefill only
    assert eng.backend.state_uploads == uploads
    for _ in range(3):
        eng.step()
    assert eng.backend.state_uploads == uploads + 1


# -------------------------------------------------------------- bucketing

def test_bucket_helpers():
    assert [next_pow2(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]
    assert bucket(3, 8) == 4 and bucket(5, 6) == 6 and bucket(6, 6) == 6
    with pytest.raises(ValueError):
        bucket(9, 8)
    assert bucket_tokens(1, 64) == 64
    assert bucket_tokens(65, 64) == 128
    assert bucket_tokens(200, 64) == 256
    assert n_buckets(8) == 4 and n_buckets(6) == 4 and n_buckets(1) == 1
