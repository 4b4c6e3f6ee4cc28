"""Paged KV cache with prefix sharing: the PR's acceptance tests.

The contract under test, at every layer:

- BIT-IDENTITY: the paged decode path (pool + page-table gather)
  produces byte-identical logits and token streams to the
  slot-contiguous path — greedy, sampled, superstep k > 1, draft-verify,
  and the int8 KV codec all included. The gather materializes exactly
  the operands the dense path reads, so the masked-softmax arithmetic
  never changes.
- PREFIX SHARING: identical prompt prefixes map to shared read-only
  pages (hash-of-prefix dedup at admission); the first divergent write
  copy-on-writes a private page; released pages stay resident cold and
  serve future hits until evicted.
- CONTAINMENT: pool exhaustion at admission refuses typed
  (`PagePoolExhaustedError`, a `MemoryPressureError`) without touching
  other requests; mid-stream exhaustion rides the OOM/degradation
  machinery (chaos coverage in test_serving_chaos.py).
- STEADY STATE: past warmup the paged loop performs zero traces/
  compiles and adds ZERO host syncs — page bookkeeping is pure host
  numpy on the existing dispatch/fetch boundaries.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import monitoring as mon
from deeplearning4j_tpu.generation import BertDecoder, GenerationServer
from deeplearning4j_tpu.generation.paging import NULL_PAGE, PageAllocator
from deeplearning4j_tpu.kernels import gather_kv_pages
from deeplearning4j_tpu.models.bert import bert_tiny, init_bert_params
from deeplearning4j_tpu.resilience.errors import (MemoryPressureError,
                                                  PagePoolExhaustedError)

PS = 8          # page size used by every server in this file
_CACHE = {"dir": None}


@pytest.fixture(scope="module", autouse=True)
def _exec_cache(tmp_path_factory):
    """Module-scoped FunctionStore disk tier (suite diet): the first
    warmup of each (model, knobs) shape compiles, later ones
    deserialize."""
    _CACHE["dir"] = str(tmp_path_factory.mktemp("paged-exec"))
    yield
    _CACHE["dir"] = None


@pytest.fixture(autouse=True)
def _mon_off():
    yield
    mon.disable()


@pytest.fixture(scope="module")
def bert():
    cfg = bert_tiny()
    return cfg, init_bert_params(cfg, jax.random.PRNGKey(1))


def _server(bert, paged, **kw):
    cfg, params = bert
    dkw = {}
    if paged:
        dkw = dict(page_size=PS, pool_pages=kw.pop("pool_pages", 40))
    dkw["kv_dtype"] = kw.pop("kv_dtype", "fp")
    kw.setdefault("slots", 3)
    kw.setdefault("cache_lengths", [16, 32])
    kw.setdefault("prompt_buckets", [8, 24])
    kw.setdefault("seed", 3)
    kw.setdefault("exec_cache_dir", _CACHE["dir"])
    srv = GenerationServer(BertDecoder(cfg, params, **dkw), **kw)
    srv.warmup()
    return srv


#: ragged-length mixed-sampling workload: page counts 1/2/3/1 at ps=8,
#: sampled slots prove the rng stream is untouched by paging
_WORKLOAD = [
    dict(prompt=[1, 4, 2], max_new_tokens=8),
    dict(prompt=[5, 6, 7, 8, 9, 10, 11, 12, 13], max_new_tokens=8,
         method="temperature", temperature=0.8),
    dict(prompt=list(range(1, 18)), max_new_tokens=10, method="top_k",
         temperature=0.9, top_k=3),
    dict(prompt=[2, 2, 5, 3], max_new_tokens=6),
]


def _run(srv, workload=_WORKLOAD):
    reqs = [srv.submit(**dict(w)) for w in workload]
    return [r.result(timeout=120) for r in reqs]


# ===================== allocator unit tests (pure host) ================
def test_allocator_maps_frees_and_reuses():
    a = PageAllocator(6, 4)            # 5 allocatable pages
    w = a.admit_slot(0, list(range(10)), 12)   # 3 pages (2 full + tail)
    assert w.shape == (3,) and (w > NULL_PAGE).all()
    occ = a.occupancy()
    assert occ["pages_mapped"] == 3 and occ["pages_free"] == 2
    # a second identical prompt shares ALL THREE pages (tail included)
    w2 = a.admit_slot(1, list(range(10)), 12)
    assert (w2 == NULL_PAGE).all()     # nothing to write again
    assert a.stats["prefix_hits"] == 1 and a.stats["pages_reused"] == 3
    assert a.occupancy()["pages_shared"] == 3
    # releasing both slots leaves the pages COLD (resident, refs 0)
    a.release_slot(0)
    a.release_slot(1)
    occ = a.occupancy()
    assert occ["pages_cold"] == 3 and occ["pages_mapped"] == 0
    # ...and a third identical admission hits them all again
    w3 = a.admit_slot(2, list(range(10)), 12)
    assert (w3 == NULL_PAGE).all()


def test_allocator_prefix_divergence_shares_only_common_pages():
    a = PageAllocator(12, 4)
    p = list(range(20, 30))            # 10 tokens: 2 full + tail
    a.admit_slot(0, p, 12)
    q = p[:8] + [99, 98]               # same 2 full pages, new tail
    w = a.admit_slot(1, q, 12)
    assert (w[:2] == NULL_PAGE).all() and w[2] > NULL_PAGE
    assert a.stats["pages_reused"] == 2


def test_allocator_cow_and_write_coverage():
    a = PageAllocator(10, 4)
    a.admit_slot(0, list(range(10)), 12)       # rows 0..9, tail page 2
    cow = a.ensure_range(0, 10, 13)    # next write rows 10..13
    # the tail page (logical 2) was keyed → exactly one (src, dst) copy
    # plus a fresh private page for logical page 3
    assert len(cow) == 1
    src, dst = cow[0]
    assert src != dst and a.stats["cow_copies"] == 1
    tab = a.build_table(1, 4)
    assert tab.shape == (1, 4)
    assert tab[0, 2] == dst            # table re-pointed to the copy
    assert tab[0, 3] > NULL_PAGE       # coverage extended
    assert a.ensure_range(0, 10, 13) == []     # idempotent


def test_allocator_exhaustion_rolls_back_and_evicts_cold():
    a = PageAllocator(4, 4)            # 3 allocatable
    with pytest.raises(PagePoolExhaustedError) as ei:
        a.admit_slot(0, list(range(16)), 16)   # needs 4 pages
    assert "RESOURCE_EXHAUSTED" in str(ei.value)
    # rollback is COMPLETE: no slot mapping, no poisoned registry
    # entries pointing at never-written pages, every page free again
    occ = a.occupancy()
    assert occ["pages_free"] == 3 and occ["pages_cold"] == 0
    # cold pages are eviction currency: a resident-but-released prefix
    # is reclaimed LRU instead of failing the allocation
    a.admit_slot(0, list(range(8)), 8)
    a.release_slot(0)                  # 2 cold pages
    a.admit_slot(1, [7, 7, 7, 7, 7], 8)        # 2 pages: 1 free + evict
    assert a.stats["evictions"] >= 1
    assert a.occupancy()["pages_mapped"] == 2


def test_allocator_pbucket_in_dedup_key():
    # same tokens prefillled under a DIFFERENT prompt bucket ran a
    # different executable — bit-determinism forbids sharing the bytes
    a = PageAllocator(10, 4)
    a.admit_slot(0, list(range(8)), 8)
    w = a.admit_slot(1, list(range(8)), 12)
    # wrow pads to the bucket's page count; both REAL pages are fresh
    assert (w[:2] > NULL_PAGE).all() and w[2] == NULL_PAGE
    assert a.stats["prefix_hits"] == 0


# ===================== kernel gather helpers ==========================
def test_gather_kv_pages_layout():
    # rows major, width minor: K/V pages (P, ps, H·D), scale pages
    # (P, ps, H) — one view helper serves both
    P, H, ps, D = 5, 2, 4, 3
    pool = jnp.arange(P * ps * H * D, dtype=jnp.float32).reshape(
        P, ps, H * D)
    tab = jnp.asarray([[2, 0], [1, 4]], jnp.int32)
    out = gather_kv_pages(pool, tab)
    assert out.shape == (2, 2 * ps, H * D)
    got = np.asarray(out)
    assert np.array_equal(got[0, :ps], np.asarray(pool[2]))
    assert np.array_equal(got[1, ps:], np.asarray(pool[4]))
    spool = jnp.arange(P * ps * H, dtype=jnp.float32).reshape(P, ps, H)
    sout = gather_kv_pages(spool, tab)
    assert sout.shape == (2, 2 * ps, H)
    assert np.array_equal(np.asarray(sout)[0, :ps], np.asarray(spool[2]))
    with pytest.raises(ValueError, match=r"\(P, ps, W\)"):
        gather_kv_pages(pool.reshape(P, ps, H, D), tab)


# ===================== server bit-identity ============================
def test_paged_streams_bit_identical_mixed_sampling(bert):
    """ACCEPTANCE: greedy + temperature + top-k streams from the paged
    server are token-identical to the slot-contiguous server, on a
    ragged workload that spans prompt buckets and cache rungs."""
    dense = _server(bert, paged=False)
    try:
        want = _run(dense)
    finally:
        dense.shutdown()
    srv = _server(bert, paged=True)
    try:
        assert _run(srv) == want
        occ = srv.status()["page_pool"]
        assert occ["pages_total"] == 39 and occ["page_size"] == PS
        # every retired request's private pages went back to the free
        # list; its prompt pages stayed resident cold
        assert occ["pages_mapped"] == 0 and occ["pages_cold"] > 0
        # ragged tails copy-on-wrote before their first generated row
        assert occ["cow_copies"] >= 1
    finally:
        srv.shutdown()


@pytest.mark.slow   # suite diet (ISSUE 19): ~30 s — compiles four more
# store identities just to cross int8 × superstep × paging; each factor
# keeps a fast-lane twin: paged-vs-dense bit-identity via
# test_paged_streams_bit_identical_mixed_sampling, the int8 KV codec
# via test_quantize.py::test_int8_kv_cache_decode_matches_fp, and
# multi-token blocks through the page index via
# test_paged_draft_verify_bit_identical
def test_paged_superstep_int8_bit_identical(bert):
    """Superstep k=3 blocks + the int8 KV codec through the paged read
    path: scale pages gather alongside payload pages, streams stay
    token-identical (int8-vs-int8 across layouts is EXACT — the same
    quantized bytes feed the same arithmetic)."""
    dense = _server(bert, paged=False, kv_dtype="int8", superstep=3)
    try:
        want = _run(dense)
    finally:
        dense.shutdown()
    srv = _server(bert, paged=True, kv_dtype="int8", superstep=3)
    try:
        assert _run(srv) == want
    finally:
        srv.shutdown()


def test_paged_draft_verify_bit_identical(bert):
    """The drafting verify dispatch reads through the same page index
    as the superstep scan: greedy streams with draft=2 equal the
    undrafted dense streams (drafting exactness composes with paging)."""
    wl = [dict(prompt=[1, 4, 2, 1, 4, 2], max_new_tokens=10),
          dict(prompt=[2, 2, 5, 3], max_new_tokens=8)]
    dense = _server(bert, paged=False)
    try:
        want = _run(dense, wl)
    finally:
        dense.shutdown()
    srv = _server(bert, paged=True, draft=2)
    try:
        assert _run(srv, wl) == want
        assert srv.stats["supersteps"] > 0
    finally:
        srv.shutdown()


def test_prefix_sharing_dedups_across_requests(bert):
    """Two identical prompts: the second admission maps the first's
    resident pages (full pages AND the tail), writes nothing but its
    CoW copy, and still streams identically."""
    srv = _server(bert, paged=True, cache_lengths=[32],
                  prompt_buckets=[24])
    try:
        p = list(range(1, 18))                 # 3 pages: 2 full + tail
        a = srv.generate(p, max_new_tokens=4, timeout=120)
        st0 = dict(srv._pages.stats)
        b = srv.generate(p, max_new_tokens=4, timeout=120)
        assert a == b
        st = srv._pages.stats
        assert st["prefix_hits"] == st0["prefix_hits"] + 1
        assert st["pages_reused"] >= st0["pages_reused"] + 3
        # the shared tail page copy-on-wrote before generation
        assert st["cow_copies"] >= st0["cow_copies"] + 1
    finally:
        srv.shutdown()


def test_pool_exhaustion_refuses_typed_and_contains(bert):
    """Admission-time pool exhaustion: the too-big request fails with
    the typed PagePoolExhaustedError (a MemoryPressureError — the
    degradation-ladder family), the server stays up, and a fitting
    request admitted right after serves normally."""
    srv = _server(bert, paged=True, pool_pages=3,   # 2 pages = 16 rows
                  cache_lengths=[32], prompt_buckets=[24], slots=2)
    try:
        big = srv.submit(list(range(1, 18)), max_new_tokens=4)  # 3 pages
        with pytest.raises(PagePoolExhaustedError):
            big.result(timeout=120)
        assert isinstance(big.error, MemoryPressureError)
        assert srv.serving_state()["state"] != "dead"
        assert len(srv.generate([1, 2, 3], max_new_tokens=4,
                                timeout=120)) == 4
    finally:
        srv.shutdown()


def test_paged_growth_is_host_side_relabel(bert):
    """Rung growth on a paged server dispatches nothing: no grow
    executables exist at all, and an admission that needs the bigger
    rung just widens the page table the next dispatch reads."""
    srv = _server(bert, paged=True)
    try:
        assert not any(str(k[0]).startswith("grow_to")
                       for k in srv._exes)
        assert srv._rung == 16
        toks = srv.generate(list(range(1, 18)), max_new_tokens=10,
                            timeout=120)       # needs rung 32
        assert len(toks) == 10
        assert srv._rung == 32
    finally:
        srv.shutdown()


def test_paged_steady_state_zero_compiles_zero_new_syncs(bert,
                                                         monkeypatch):
    """ACCEPTANCE (fast-path): past warmup the paged loop — page
    allocation, CoW page copies, table builds included — performs zero
    traces/compiles, and the host-sync ledger stays EXACTLY one fetch
    per decode block plus one per admission: paging adds no syncs."""
    from deeplearning4j_tpu.runtime import executables as ex
    srv = _server(bert, paged=True)
    try:
        def boom(*a, **k):
            raise AssertionError("paged steady state tried to compile")

        monkeypatch.setattr(ex.FunctionStore, "load_or_compile", boom)
        monkeypatch.setattr(jax, "jit", boom)
        traces = srv._store.trace_calls
        fetches0, steps0 = srv.token_fetches, srv.stats["steps"]
        r1 = srv.submit([1, 2, 3, 4, 5, 6, 7, 8, 9], max_new_tokens=6)
        r2 = srv.submit([5, 6], max_new_tokens=4)
        assert len(r1.result(timeout=120)) == 6
        assert len(r2.result(timeout=120)) == 4
        assert srv._store.trace_calls == traces

        def balanced():
            return (srv.token_fetches - fetches0
                    == (srv.stats["steps"] - steps0) + 2)

        # result() returns from INSIDE the last block's delivery, after
        # its fetch was counted and before its step is: read the ledger
        # once the decode thread has finished that block
        deadline = time.monotonic() + 5.0
        while not balanced() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert balanced(), (srv.token_fetches - fetches0,
                            srv.stats["steps"] - steps0)
        assert srv._pages.stats["cow_copies"] >= 1  # CoW did happen
    finally:
        srv.shutdown()


def test_paged_metrics_and_health_surface(bert):
    """dl4j.gen.{pages_active,pages_shared,page_evictions,prefix_hits}
    emit behind the enabled-guard, and /health's serving section plus
    /generation's status() carry the pool occupancy dict."""
    srv = _server(bert, paged=True, cache_lengths=[32],
                  prompt_buckets=[24])
    try:
        mon.enable()
        p = list(range(1, 18))
        srv.generate(p, max_new_tokens=4, timeout=120)
        srv.generate(p, max_new_tokens=4, timeout=120)
        reg = mon.get_registry()
        assert reg.gauge(mon.GEN_PAGES_ACTIVE).value > 0
        assert reg.counter(mon.GEN_PREFIX_HITS).value >= 1
        sstate = srv.serving_state()
        assert sstate["page_pool"]["pages_cold"] > 0
        assert sstate["page_pool"]["prefix_hits"] >= 1
        from deeplearning4j_tpu.generation import server as gsrv
        agg = gsrv.status()["servers"]
        assert any(s.get("paged") and "page_pool" in s for s in agg)
    finally:
        srv.shutdown()


def test_paged_decoder_knob_validation(bert):
    cfg, params = bert
    with pytest.raises(ValueError):
        BertDecoder(cfg, params, page_size=8)          # pool required
    with pytest.raises(ValueError):
        BertDecoder(cfg, params, pool_pages=16)        # size required
    with pytest.raises(ValueError):
        BertDecoder(cfg, params, page_size=8, pool_pages=1)
    with pytest.raises(ValueError):
        # rungs must be whole pages
        GenerationServer(BertDecoder(cfg, params, page_size=8,
                                     pool_pages=16),
                         cache_lengths=[12])
